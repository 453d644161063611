"""The ``skipped_sweep_pct`` reader on a trace written in the layout of a
TPU trace, with known numbers (the layout of ``test_reducers.py``)."""

import pytest

from test_reducers import CFG_BTC, _run, _tpu_trace, metric

NAME = "jit_pallas_search_candidates(13720780856515344583)"
# between two runs that the trace's start and stop cut (1 µs each, no
# kernel op inside), a run of the chained sweep with its kernel op, one
# that skipped on the device (a cond op, no kernel op), and a roll that
# is not the sweep
PROGRAMS = [(0.0, 1e-6, NAME), (0.01, 0.125, NAME), (0.14, 3e-6, NAME),
            (0.2, 0.05, "jit_roll(16991100186894038334)"), (0.999, 1e-6, NAME)]
OPS = [(0.0, 1e-6, "slice.2"), (0.01, 0.125, "pallas_search_candidates.1"),
       (0.14, 5e-7, "cond.8"), (0.2, 0.05, "fusion.4")]


def test_skipped_sweep_pct(tmp_path):
    trace = _tpu_trace(tmp_path, PROGRAMS, OPS, [], 1.0)
    assert metric("skipped_sweep_pct")(_run(trace=trace)) == pytest.approx(50.0)
    # the kernel-rate reader leaves the skipped and cut runs out as short
    want = CFG_BTC["miner"]["slab"] / 0.125 / 1e9
    assert metric("sha256d_search_gnonce_per_s")(_run(trace=trace)) == pytest.approx(want)


@pytest.mark.parametrize("keep", [[3], [0, 4]], ids=["no_sweep", "only_cut_runs"])
def test_skipped_sweep_pct_finds_nothing_to_read(tmp_path, keep):
    trace = _tpu_trace(tmp_path, [PROGRAMS[i] for i in keep], OPS, [], 1.0)
    assert metric("skipped_sweep_pct")(_run(trace=trace)) is None
    assert metric("skipped_sweep_pct")(_run()) is None
