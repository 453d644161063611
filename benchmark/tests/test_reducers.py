"""The reductions from a run's records to metrics, on recorded inputs.

- ``fixtures/compile_log.txt``: the compile log of a TPU v5 lite miner
  child, as the benchmark's worker writes it (the candidate kernel's
  first job on the genesis header); ``fixtures/compile_log_scrypt.txt``:
  the lines of a scrypt miner's log that lower its step.
- A trace recorded here with ``jax.profiler`` on the CPU: it has no
  device, so every device reading finds nothing and gives nothing.
- A trace written below in the layout of a TPU trace (a
  ``/device:TPU:0`` process with ``XLA Modules`` and ``XLA Ops`` lines,
  names and args as the chip records them), with known numbers.
"""

import gzip
import json
import os
import sys
from types import SimpleNamespace

import pytest

from conftest import BENCH, load

sys.path.insert(0, BENCH)
from traces import compile_events, load_trace, program_args, union  # noqa: E402

FIX = os.path.join(BENCH, "tests", "fixtures")
CFG_BTC = json.load(open(os.path.join(BENCH, "configs", "btc-sha256d.json")))
CFG_LTC = json.load(open(os.path.join(BENCH, "configs", "ltc-scrypt.json")))
PEAKS = json.load(open(os.path.join(BENCH, "peaks.json")))["devices"]["TPU v5 lite"]


def metric(name):
    return load(f"metrics/{name}.py").read


def _run(**kw):
    base = dict(records=[], compiles=[], program_args={}, trace=None, window_wall=0.0,
                config=CFG_BTC, peaks=PEAKS, chips=1, setup_s=1.0)
    base.update(kw)
    return SimpleNamespace(**base)


def test_union():
    assert union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]


def test_compile_log():
    with open(os.path.join(FIX, "compile_log.txt")) as fh:
        events = compile_events(fh.read())
    assert {e["kind"] for e in events} == {"trace", "lower", "compile"}
    kernel = [e for e in events if e["name"] == "pallas_search_candidates"]
    assert kernel and kernel[0]["end"] - kernel[0]["start"] == pytest.approx(16.695046186)
    run = _run(compiles=events, window_wall=1792071300.0)
    # nested traces fall inside the kernel's 16.7 s span
    assert metric("setup_trace_s")(run) == pytest.approx(16.7095, abs=1e-3)
    # the XLA compile that ends after 1792071300.0 is not set-up ...
    assert metric("setup_compile_s")(run) == pytest.approx(1.853258371, abs=1e-3)
    # ... it is in the window
    assert metric("window_compiles")(run) == 3
    run.window_wall = 1792071400.0
    assert metric("setup_compile_s")(run) == pytest.approx(13.2908, abs=1e-3)
    assert metric("window_compiles")(run) == 0


def test_program_args():
    with open(os.path.join(FIX, "compile_log_scrypt.txt")) as fh:
        args = program_args(fh.read())
    assert args == {"_scrypt_step": [[19], [16384], [8]]}
    with open(os.path.join(FIX, "compile_log.txt")) as fh:
        assert program_args(fh.read()) == {}


def test_cpu_trace_has_no_device(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 3 + 1).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (tmp_path / "window.json").write_text(json.dumps({"start": 0.0, "stop": 0.5}))
    trace = load_trace(str(tmp_path))
    assert trace is not None and trace.host and not trace.devices
    run = _run(trace=trace)
    for name in ("device_idle_pct", "sha256d_search_gnonce_per_s"):
        assert metric(name)(run) is None
    assert metric("romix_roofline")(_run(trace=trace, config=CFG_LTC)) is None


def _tpu_trace(tmp_path, programs, ops, host, window_s):
    """Chrome trace events in the TPU layout; times in seconds."""
    ev = [
        {"ph": "M", "pid": 3, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name", "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 701, "name": "process_name", "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 701, "tid": 9, "name": "thread_name", "args": {"name": "python3"}},
    ]
    for tid, rows in ((2, programs), (3, ops)):
        for start, dur, name in rows:
            ev.append({"ph": "X", "pid": 3, "tid": tid, "ts": start * 1e6,
                       "dur": dur * 1e6, "name": name, "args": {"run_id": "1"}})
    for start, dur, name in host:
        ev.append({"ph": "X", "pid": 701, "tid": 9, "ts": start * 1e6,
                   "dur": dur * 1e6, "name": name})
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as fh:
        json.dump({"traceEvents": ev}, fh)
    (tmp_path / "window.json").write_text(json.dumps({"start": 10.0, "stop": 10.0 + window_s}))
    return load_trace(str(tmp_path))


def test_tpu_trace_sha256d(tmp_path):
    # four full 0.125 s candidate-kernel runs, one that stopped early,
    # a roll, and gaps of 0.05 s between them in a 1 s window
    programs, ops, t = [], [], 0.0
    for dur in (0.125, 0.125, 0.01, 0.125, 0.125):
        programs.append((t, dur, "jit_pallas_search_candidates(5815203164701423444)"))
        ops.append((t, dur, "pallas_search_candidates.1"))
        t += dur + 0.05
    programs.append((t, 0.05, "jit_roll(16991100186894038334)"))
    ops.append((t, 0.05, "fusion.4"))
    trace = _tpu_trace(tmp_path, programs, ops,
                       [(0.0, 1.0, "$worker.py:1 run"), (0.1, 0.1, "$time sleep")], 1.0)
    busy = 4 * 0.125 + 0.01 + 0.05
    assert trace.busy_s() == pytest.approx(busy)
    run = _run(trace=trace)
    assert metric("device_idle_pct")(run) == pytest.approx(100 * (1 - busy))
    want = 4 * CFG_BTC["miner"]["slab"] / (4 * 0.125) / 1e9
    assert metric("sha256d_search_gnonce_per_s")(run) == pytest.approx(want)
    top = trace.top_programs()
    assert top[0] == ["jit_pallas_search_candidates", pytest.approx(0.51)]
    gaps = trace.idle_gaps()
    assert sorted(name for name, _ in gaps) == ["$time sleep"] + ["$worker.py:1 run"] * 4
    assert all(length == pytest.approx(0.05) for _, length in gaps)


def test_tpu_trace_romix(tmp_path):
    programs = [(0.0, 0.6, "jit__scrypt_step(1917499911031059357)"),
                (0.65, 0.6, "jit__scrypt_step(1917499911031059357)")]
    trace = _tpu_trace(tmp_path, programs, [(s, d, "while.602") for s, d, _ in programs],
                       [], 1.5)
    run = _run(trace=trace, config=CFG_LTC)
    assert metric("romix_roofline")(run) is None  # the step's batch is not known
    run.program_args = {"_scrypt_step": [[19], [16384], [8]]}
    moved = 2 * 16384 * 2 * 128 * 1 * 1024
    want = 100 * moved / 1.2 / PEAKS["hbm_bytes_per_s"]
    assert metric("romix_roofline")(run) == pytest.approx(want)
    assert 0 < want < 100
    assert metric("sha256d_search_gnonce_per_s")(run) is None


def test_miner_probe_writes_after_the_first_job_and_at_close(tmp_path):
    import jax
    import jax.numpy as jnp
    import worker_main

    jax.jit(lambda x: x * 2)(jnp.ones(1024)).block_until_ready()

    class Miner:
        backend, lanes, span, progress_cb = "fake", 1, 1, None

        def mine(self, request):
            yield None
            yield "result"

    worker_main.EVENTS[:] = [[0.0, "gc", "collected 5", 0.02]]
    request = SimpleNamespace(job_id=7, chunk_id=3, lower=10, upper=20)
    probe = worker_main.MinerProbe(Miner(), str(tmp_path))
    memory = tmp_path / "memory.json"
    assert list(probe.mine(request)) == [None, "result"]
    reading = json.loads(memory.read_text())
    assert reading["peak_bytes"] >= reading["fullest"]["largest_program_bytes"] > 0
    memory.unlink()
    assert list(probe.mine(request)) == [None, "result"]
    assert not memory.exists()  # window jobs pay nothing
    assert not (tmp_path / "steps.jsonl").exists()
    probe.close()
    assert memory.exists()
    rows = [json.loads(line) for line in (tmp_path / "steps.jsonl").read_text().splitlines()]
    assert [r[1] for r in rows] == ["gc"] + ["start", "step", "result"] * 2
    assert rows[1][2:] == [7, 3, 10, 20]


def test_pause_probes_record_long_compiles_and_collections(monkeypatch):
    import gc

    import jax
    import jax.numpy as jnp
    import worker_main

    monkeypatch.setattr(worker_main, "PAUSE_S", 0.0)
    monkeypatch.setattr(worker_main, "EVENTS", [])
    callbacks = list(gc.callbacks)
    try:
        worker_main.record_pauses()
        jax.jit(lambda x: x - 5)(jnp.ones(7)).block_until_ready()
        gc.collect()
    finally:
        gc.callbacks[:] = callbacks
    kinds = {row[1] for row in worker_main.EVENTS}
    assert kinds == {"jax", "gc"}
    assert any("backend_compile" in row[2] for row in worker_main.EVENTS)


def test_miner_pauses_names_the_longest_gap_in_the_window(tmp_path):
    import run

    rows = [[1.0, "start", 1, 1, 0, 9], [5.0, "step"],  # set-up: not the window
            [10.0, "start", 3, 5, 0, 9], [10.3, "step"], [13.4, "step"],
            [13.5, "gc", "collected 9", 0.02], [13.6, "result"]]
    (tmp_path / "steps.jsonl").write_text("".join(json.dumps(r) + "\n" for r in rows))
    note = run.miner_pauses(str(tmp_path), 9.0)
    assert "longest gap 3.100 s (step -> step)" in note
    assert "1 compiles or full collections" in note
    assert run.miner_pauses(str(tmp_path / "none"), 9.0) == "the miner recorded no steps"
