"""The Stratum template-churn cell, ``btc.rolled.churn``, on the CPU.

- The cell through ``run.main`` with a ``--backend jax`` worker, whose
  miner rolls each fresh template through the device roll (a
  ``--backend cpu`` worker would bypass it), and the traffic cut as
  ``test_harness._small`` cuts ``rolled_share``: its answers agree with
  ``btc-stratum-v1.py``, and planted ``flip_nonce``, ``skip_half`` and
  ``no_search`` faults do not.
- The readers of the rolled path, on traces written in the layout of a
  TPU trace under the run's own directory, on a trace of a miner without
  roll spans (the parent's), on a genesis trace and on a trace recorded
  on the CPU.
"""

import gzip
import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

from conftest import BENCH, ROOT, load
from test_harness import FAULT_WORKER, _run

sys.path.insert(0, BENCH)
import run  # noqa: E402
from traces import load_trace  # noqa: E402

CELL = "btc.rolled.churn"
MIX = "rolled_churn"
READERS = ("template_roll_ms", "rolled_search_gnonce_per_s")


def _small(spec: dict) -> dict:
    """The mix at a size a host mines in a few milliseconds a job, each
    job still on a fresh template."""
    spec["per_run"]["bits"] = "0x2000ffff"
    spec["per_job"].update(lo={"step": [0, 1 << 32]}, span=3000)
    return spec


@pytest.fixture
def churn_bench(tmp_path, monkeypatch):
    """A copy of the benchmark whose churn traffic is cut to CPU sizes,
    mined by a ``--backend jax`` worker of 4 roll rows a window on the
    CPU, in the worker's miner child."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    path = tmp_path / "benchmark" / "traffic" / f"{MIX}.json"
    path.write_text(json.dumps(_small(json.loads(path.read_text()))))
    path = tmp_path / "benchmark" / "configs" / "btc-stratum-v1.json"
    cfg = json.loads(path.read_text())
    assert cfg["worker"] == ["--backend", "tpu"]
    path.write_text(json.dumps(dict(cfg, worker=["--backend", "jax"],
                                    miner={"roll_batch": 4})))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(run, "find_device", lambda *a: (
        {"platform": "cpu", "kind": "cpu", "count": 1}, {}))
    monkeypatch.setattr(run, "read_memory", lambda out_dir: {"peak_bytes": 0})
    return tmp_path


def _set_mix(bench_root, **fields):
    path = bench_root / "benchmark" / "traffic" / f"{MIX}.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **fields)))


def test_churn_cell_is_correct(churn_bench):
    _set_mix(churn_bench, sample="all")
    rc, out = _run(churn_bench, CELL, seconds=3)
    assert rc == 0 and out["correct"], out
    assert out["attempted"] > 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"hashrate_per_chip", "setup_s"}
    jobs = [json.loads(line) for line in
            (churn_bench / run.RUNS_DIR / CELL / "jobs.jsonl").read_text().splitlines()]
    assert all(j["answer"]["found"] for j in jobs)


@pytest.mark.parametrize("fault", ["flip_nonce", "skip_half", "no_search"])
def test_churn_cell_sees_planted_faults(churn_bench, fault, monkeypatch):
    monkeypatch.setenv("TPUMINTER_BENCH_FAULT", fault)
    monkeypatch.setattr(run, "ANSWER_WAIT_S", 5.0)
    _set_mix(churn_bench, warmup=[], sample="all")
    rc, out = _run(churn_bench, CELL, seconds=2, launcher=FAULT_WORKER)
    assert rc != 0 or not out["correct"], out


def test_churn_check_sees_skipped_work_at_the_cells_size():
    """At the committed size most jobs hold no share, and a not-found
    answer is checked only where the reference recomputes its job. The
    recomputed jobs have to make a miner that skips work read not
    correct on all but a sliver of seeds: ``no_search`` passes only if
    no recomputed job holds a share, ``skip_half`` only if none holds
    one in the half it skips."""
    import math

    ref = load("configs/btc-stratum-v1.py")
    mix = json.loads(open(os.path.join(BENCH, "traffic", f"{MIX}.json")).read())
    sample, span = mix["sample"], mix["per_job"]["span"]
    target = ref.bits_to_target(int(mix["per_run"]["bits"], 16))
    shares = span * (target + 1) / 2 ** 256  # a job's expected shares
    assert 0.2 < shares < 0.3
    assert math.exp(-sample * shares) < 1e-6      # no_search
    assert math.exp(-sample * shares / 2) < 1e-3  # skip_half


# -- the readers -------------------------------------------------------------

ROLL, AWAIT, WINNER = "tpuminter.roll", "tpuminter.await_chunk", "tpuminter.winner"
SWEEP, ROLL_PROGRAM = "jit__pallas_batched_candidate_sweep", "jit_extranonce_roll"


def read(name, trace, root):
    """The reader's value; the kernel rate's reader looks for the run's
    trace file under ``root``."""
    kw = {"root": str(root)} if name == "rolled_search_gnonce_per_s" else {}
    return load(f"metrics/{name}.py").read(SimpleNamespace(trace=trace), **kw)


def _trace(root, programs, spans, window_s=1.0, cell=CELL):
    """A TPU-layout trace in ``root``'s run directory of ``cell``:
    ``programs`` are ``(name, start, end)`` runs on the chip's ``XLA
    Modules`` line (each with one op on its ``XLA Ops`` line), ``spans``
    ``(name, start, end, args)`` on a host thread, in seconds."""
    ev = [
        {"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 1, "name": "thread_name", "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 701, "name": "process_name", "args": {"name": "/host:CPU"}},
    ]
    for name, start, end in programs:
        ev.append({"ph": "X", "pid": 3, "tid": 1, "ts": start * 1e6,
                   "dur": (end - start) * 1e6, "name": f"{name}(42)"})
        ev.append({"ph": "X", "pid": 3, "tid": 2, "ts": start * 1e6,
                   "dur": (end - start) * 1e6, "name": "custom-call.1"})
    for name, start, end, args in spans:
        ev.append({"ph": "X", "pid": 701, "tid": 9, "ts": start * 1e6,
                   "dur": (end - start) * 1e6, "name": name,
                   "args": {k: str(v) for k, v in args.items()}})
    trace_dir = root / run.RUNS_DIR / cell / "trace"
    d = trace_dir / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as fh:
        json.dump({"traceEvents": ev}, fh)
    (trace_dir / "window.json").write_text(json.dumps({"start": 10.0, "stop": 10.0 + window_s}))
    return load_trace(str(trace_dir))


def _roll(job, chunk, start, dur, nonces):
    return (ROLL, start, start + dur, {"job": job, "chunk": chunk, "nonces": nonces})


#: a churn window: a sweep dispatched before the trace opened, still on
#: the device at its start; job 1's two chunks (the first's one window,
#: 2^29 indices; the second's two, 2^28 and a 3 x 2^26-index remainder)
#: find nothing; job 2's chunk sends the winner; job 3's sweep is cut by
#: the trace's stop a microsecond in, as a chip's trace records it
CHURN = dict(
    programs=[
        (SWEEP, 0.000, 0.020),
        (ROLL_PROGRAM, 0.030, 0.031), (SWEEP, 0.031, 0.531),
        (ROLL_PROGRAM, 0.540, 0.541), (SWEEP, 0.541, 0.791),
        (ROLL_PROGRAM, 0.792, 0.793), (SWEEP, 0.793, 0.993),
        (ROLL_PROGRAM, 0.995, 0.996), (SWEEP, 0.996, 1.100),
        (ROLL_PROGRAM, 1.110, 1.111), (SWEEP, 1.111, 1.111001),
    ],
    spans=[
        (AWAIT, 0.002, 0.025, {}),
        _roll(1, 10, 0.026, 0.004, 1 << 29),
        (AWAIT, 0.532, 0.536, {}),
        _roll(1, 11, 0.537, 0.003, 1 << 28),
        _roll(1, 11, 0.545, 0.001, 3 << 26),
        (AWAIT, 0.993, 0.994, {}),
        _roll(2, 12, 0.9945, 0.0005, 1 << 29),
        (WINNER, 1.101, 1.102, {"job": 2, "chunk": 12}),
        (AWAIT, 1.103, 1.104, {}),
        _roll(3, 13, 1.105, 0.002, 1 << 29),
    ],
)


def test_readers_on_a_churn_trace(tmp_path):
    trace = _trace(tmp_path, **CHURN)
    # the first roll of chunks 10, 11, 12 and 13
    assert read("template_roll_ms", trace, tmp_path) == pytest.approx(
        1e3 * (0.004 + 0.003 + 0.0005 + 0.002) / 4)
    # the sweep before the first roll and the cut last one are left
    # out; chunk 12 sent the winner; the rest pair with the rolls in order
    nonces = (1 << 29) + (1 << 28) + (3 << 26)
    assert read("rolled_search_gnonce_per_s", trace, tmp_path) == pytest.approx(
        nonces / (0.5 + 0.25 + 0.2) / 1e9)


def test_readers_take_the_runs_own_trace(tmp_path):
    """Another cell's trace under the same root, of another window, is
    not read for the roll spans' args."""
    trace = _trace(tmp_path, **CHURN)
    _trace(tmp_path, [(SWEEP, 0.0, 0.1)], [_roll(5, 5, 0.0, 0.1, 1)],
           window_s=2.0, cell="btc.diff1.genesis")
    nonces = (1 << 29) + (1 << 28) + (3 << 26)
    assert read("rolled_search_gnonce_per_s", trace, tmp_path) == pytest.approx(
        nonces / (0.5 + 0.25 + 0.2) / 1e9)


def test_rolled_rate_leaves_out_a_sweep_queued_before_the_trace(tmp_path):
    """A sweep dispatched before the trace opened that starts on the
    device after the first roll span, ahead of that span's roll program,
    pairs with no roll span."""
    programs = [(SWEEP, 0.0265, 0.0295)] + CHURN["programs"][1:]
    trace = _trace(tmp_path, programs, CHURN["spans"])
    nonces = (1 << 29) + (1 << 28) + (3 << 26)
    assert read("rolled_search_gnonce_per_s", trace, tmp_path) == pytest.approx(
        nonces / (0.5 + 0.25 + 0.2) / 1e9)


def test_readers_on_a_trace_without_roll_spans(tmp_path):
    """A miner without roll spans (the parent of this cell's program):
    the same programs, a per-template compile before each chunk."""
    trace = _trace(tmp_path, CHURN["programs"],
                   [s for s in CHURN["spans"] if s[0] != ROLL])
    for name in READERS:
        assert read(name, trace, tmp_path) is None
        assert read(name, None, tmp_path) is None


def test_readers_on_a_genesis_trace(tmp_path):
    slabs = [("jit_pallas_search_candidates", 0.13 * i, 0.13 * i + 0.129) for i in range(6)]
    trace = _trace(tmp_path, slabs, [(AWAIT, 0.0, 0.001, {})])
    for name in READERS:
        assert read(name, trace, tmp_path) is None


def test_rolled_rate_needs_the_sweep_program(tmp_path):
    """Roll spans with no batched sweep on the device (the tracking
    path's step): the roll's cost is read, no kernel rate."""
    programs = [(ROLL_PROGRAM, 0.03, 0.031), ("jit__tracking_step", 0.031, 0.5),
                (ROLL_PROGRAM, 0.5, 0.501), ("jit__tracking_step", 0.501, 0.9)]
    trace = _trace(tmp_path, programs, CHURN["spans"][:2])
    assert read("template_roll_ms", trace, tmp_path) == pytest.approx(4.0)
    assert read("rolled_search_gnonce_per_s", trace, tmp_path) is None


def test_readers_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(8)).block_until_ready()
    trace_dir = tmp_path / run.RUNS_DIR / CELL / "trace"
    jax.profiler.start_trace(str(trace_dir))
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    (trace_dir / "window.json").write_text(json.dumps({"start": 0.0, "stop": 0.5}))
    trace = load_trace(str(trace_dir))
    assert not trace.devices
    for name in READERS:
        assert read(name, trace, tmp_path) is None
