"""The readers of the miner child's program spans, on traces with known
answers.

- Traces written below in the layout of a TPU trace: a ``/device:TPU:0``
  process with an ``XLA Ops`` line, and the child's ``tpuminter.*``
  annotations on a host thread, as the profiler records them.
- A trace recorded here with ``jax.profiler`` on the CPU, with program
  spans in it and no device.
"""

import gzip
import json
import os
import sys
from types import SimpleNamespace

import pytest

from conftest import BENCH, load

sys.path.insert(0, BENCH)
from traces import load_trace  # noqa: E402

READERS = ("abandoned_sweep_pct", "cancel_lag_ms", "chunk_prepare_ms",
           "idle_awaiting_chunk_pct")
AWAIT, DISPATCH, RESOLVE, WINNER, CANCEL = (
    "tpuminter." + s for s in ("await_chunk", "dispatch", "resolve", "winner", "cancel"))


def read(name, trace):
    return load(f"metrics/{name}.py").read(SimpleNamespace(trace=trace))


def _trace(tmp_path, ops, spans, window_s):
    """A TPU-layout trace: device ops and program spans, ``(start, end)``
    or ``(start, end, name)`` in seconds."""
    ev = [
        {"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 701, "name": "process_name", "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 701, "tid": 9, "name": "thread_name", "args": {"name": "python3"}},
    ]
    for start, end in ops:
        ev.append({"ph": "X", "pid": 3, "tid": 3, "ts": start * 1e6,
                   "dur": (end - start) * 1e6, "name": "pallas_search_candidates.1"})
    for start, end, name in spans:
        ev.append({"ph": "X", "pid": 701, "tid": 9, "ts": start * 1e6,
                   "dur": (end - start) * 1e6, "name": name, "args": {"job": "4"}})
    # the Python tracer's poll of the benchmark's own trace thread
    ev.append({"ph": "X", "pid": 701, "tid": 11, "ts": 0.0, "dur": window_s * 1e6,
               "name": "$time sleep"})
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as fh:
        json.dump({"traceEvents": ev}, fh)
    (tmp_path / "window.json").write_text(json.dumps({"start": 10.0, "stop": 10.0 + window_s}))
    return load_trace(str(tmp_path))


#: genesis-shaped: the window opens inside a chunk; then a chunk whose
#: first slab wins with the slab behind it in flight, the same job's
#: pipelined chunk cancelled after three sweeps with a fourth in flight,
#: a chunk that wins in its last slab, and a chunk the window cuts
GENESIS_SPANS = [
    (0.000, 0.001, DISPATCH), (0.002, 0.120, RESOLVE),
    (0.300, 0.310, AWAIT),
    (0.312, 0.313, DISPATCH), (0.314, 0.315, DISPATCH), (0.316, 0.440, RESOLVE),
    (0.441, 0.442, WINNER),
    (0.443, 0.444, AWAIT),
    (0.446, 0.447, DISPATCH), (0.448, 0.449, DISPATCH), (0.450, 0.570, RESOLVE),
    (0.571, 0.572, DISPATCH), (0.573, 0.700, RESOLVE),
    (0.701, 0.702, DISPATCH), (0.703, 0.830, RESOLVE),
    (0.840, 0.841, CANCEL),
    (0.842, 0.900, AWAIT),
    (0.902, 0.905, DISPATCH), (0.906, 0.907, DISPATCH), (0.908, 1.030, RESOLVE),
    (1.031, 1.050, RESOLVE), (1.060, 1.061, WINNER),
    (1.062, 1.063, AWAIT),
    (1.064, 1.065, DISPATCH),
]
GENESIS_OPS = [(0.0, 0.86), (0.905, 1.07)]


def test_genesis_shaped_window(tmp_path):
    trace = _trace(tmp_path, GENESIS_OPS, GENESIS_SPANS, 1.1)
    # whole chunks: 2 sweeps (1 in flight at the win), 4 (cancelled), 2 (none left)
    assert read("abandoned_sweep_pct", trace) == pytest.approx(100 * 5 / 8)
    # the first winner's end to the cancel's start; the second has none
    assert read("cancel_lag_ms", trace) == pytest.approx(398.0)
    # 3, 3, 5 and 2 ms from an await's end to the first dispatch's end
    assert read("chunk_prepare_ms", trace) == pytest.approx(3.0)
    # the device idles 0.86-0.90 inside the third await; spans run 0-1.065 s
    assert read("idle_awaiting_chunk_pct", trace) == pytest.approx(100 * 0.04 / 1.065)


def test_scrypt_shaped_window_without_a_winner(tmp_path):
    spans = [
        (0.000, 0.500, RESOLVE),
        (0.550, 0.560, AWAIT),
        (0.561, 0.563, DISPATCH), (0.564, 0.566, DISPATCH),
        (0.567, 1.150, RESOLVE), (1.151, 1.200, RESOLVE),
        (1.200, 1.210, AWAIT),
        (1.212, 1.214, DISPATCH),
    ]
    ops = [(0.0, 0.52), (0.565, 1.205), (1.215, 1.5)]
    trace = _trace(tmp_path, ops, spans, 1.5)
    assert read("abandoned_sweep_pct", trace) == 0.0
    assert read("cancel_lag_ms", trace) is None
    assert read("chunk_prepare_ms", trace) == pytest.approx(3.5)
    # the whole first await and 5 ms of the second, over 0-1.214 s
    assert read("idle_awaiting_chunk_pct", trace) == pytest.approx(100 * 0.015 / 1.214)


def test_no_program_spans_read_nothing(tmp_path):
    trace = _trace(tmp_path, GENESIS_OPS, [], 1.1)
    assert trace.devices and trace.host
    for name in READERS:
        assert read(name, trace) is None
        assert read(name, None) is None


def test_cpu_trace_with_program_spans_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    from tpuminter.spans import span

    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for name in (AWAIT, DISPATCH, RESOLVE, WINNER):
        with span(name, job=1, chunk=2):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    (tmp_path / "window.json").write_text(json.dumps({"start": 0.0, "stop": 0.5}))
    trace = load_trace(str(tmp_path))
    assert not trace.devices
    # the spans are there, named as the readers name them ...
    assert [n for _, _, n in sorted(trace.host) if n.startswith("tpuminter.")] == [
        AWAIT, DISPATCH, RESOLVE, WINNER]
    # ... but with no device the window is no chip run
    for name in READERS:
        assert read(name, trace) is None
