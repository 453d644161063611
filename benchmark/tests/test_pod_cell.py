"""The four-chip pod cell, ``btc.pod.diff1``, on the CPU.

- The cell through ``run.main`` with its own ``--backend pod`` worker on a
  mesh of four virtual CPU devices, slab cut to 256 nonces a chip and the
  genesis traffic cut as ``test_harness._small`` cuts it: its answers
  agree with the reference, and a planted ``skip_half`` fault does not.
- The readers of the pod candidate sweep, on four-chip traces written in
  the layout of a TPU trace (an ``XLA Modules`` and an ``XLA Ops`` line a
  chip), on a single-chip genesis trace and on a trace recorded on the
  CPU.
"""

import gzip
import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

from conftest import BENCH, ROOT, load
from test_harness import FAULT_WORKER, _run, _small

sys.path.insert(0, BENCH)
import run  # noqa: E402
from traces import load_trace  # noqa: E402

CELL = "btc.pod.diff1"
READERS = ("pod_stripes_per_sweep", "pod_reduce_wait_pct", "pod_search_gnonce_per_s")
SLAB = 1 << 27


@pytest.fixture
def pod_bench(tmp_path, monkeypatch):
    """A copy of the benchmark whose genesis traffic is cut to CPU sizes
    and whose pod configuration mines 256 nonces a chip a stripe, on four
    virtual CPU devices in the worker's miner child."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    path = tmp_path / "benchmark" / "traffic" / "genesis_diff1.json"
    path.write_text(json.dumps(_small("genesis_diff1", json.loads(path.read_text()))))
    path = tmp_path / "benchmark" / "configs" / "btc-sha256d-pod4.json"
    cfg = json.loads(path.read_text())
    assert cfg["worker"] == ["--backend", "pod"]
    path.write_text(json.dumps(dict(cfg, miner=dict(cfg["miner"], slab=256))))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    monkeypatch.setattr(run, "find_device", lambda *a: (
        {"platform": "cpu", "kind": "cpu", "count": 4}, {}))
    monkeypatch.setattr(run, "read_memory", lambda out_dir: {"peak_bytes": 0})
    return tmp_path


def test_pod_cell_is_correct(pod_bench):
    rc, out = _run(pod_bench, CELL, seconds=3)
    assert rc == 0 and out["correct"], out
    assert out["attempted"] > 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"hashrate_per_chip", "time_to_block_p50_ms",
                                   "time_to_block_p80_ms", "setup_s"}
    log = (pod_bench / run.RUNS_DIR / CELL / "worker.log").read_text()
    assert "pod mesh: 4 devices" in log


def test_pod_cell_sees_skip_half(pod_bench, monkeypatch):
    monkeypatch.setenv("TPUMINTER_BENCH_FAULT", "skip_half")
    monkeypatch.setattr(run, "ANSWER_WAIT_S", 5.0)
    path = pod_bench / "benchmark" / "traffic" / "genesis_diff1.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), warmup=[], sample="all")))
    rc, out = _run(pod_bench, CELL, seconds=2, launcher=FAULT_WORKER)
    assert rc != 0 or not out["correct"], out


def read(name, trace, slab=SLAB):
    return load(f"metrics/{name}.py").read(
        SimpleNamespace(trace=trace, config={"miner": {"slab": slab}}))


def _trace(tmp_path, chips, window_s=1.0):
    """A TPU-layout trace: per chip, ``[(program, start, end, [(kernel
    start, kernel end), ...])]`` in seconds."""
    ev = []
    for d, runs in enumerate(chips):
        pid = 3 + d
        ev += [
            {"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": f"/device:TPU:{d}"}},
            {"ph": "M", "pid": pid, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": pid, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
        ]
        for program, start, end, kernels in runs:
            ev.append({"ph": "X", "pid": pid, "tid": 1, "ts": start * 1e6,
                       "dur": (end - start) * 1e6, "name": f"{program}(123)"})
            for i, (ks, ke) in enumerate(kernels):
                ev.append({"ph": "X", "pid": pid, "tid": 2, "ts": ks * 1e6,
                           "dur": (ke - ks) * 1e6, "name": f"pallas_search_candidates.{i + 1}"})
                ev.append({"ph": "X", "pid": pid, "tid": 2, "ts": ke * 1e6,
                           "dur": 1e-5 * 1e6, "name": "all-reduce.3"})
    ev += [
        {"ph": "M", "pid": 701, "name": "process_name", "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 701, "tid": 9, "ts": 0.0, "dur": window_s * 1e6,
         "name": "$time sleep"},
    ]
    d = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as fh:
        json.dump({"traceEvents": ev}, fh)
    (tmp_path / "window.json").write_text(json.dumps({"start": 10.0, "stop": 10.0 + window_s}))
    return load_trace(str(tmp_path))


POD = "jit_pod_candidate_sweep"


def _genesis_pod(chip):
    """Chip ``chip``'s runs: a winner's sweep, stopped by the or-reduce
    after its first stripe, in which chip 0 found the winner 0.04 s into
    its slab and waited for the others' 0.1 s slabs; then a sweep of all
    four stripes; then a sweep the trace cut before its first kernel."""
    first = 0.04 if chip == 0 else 0.1
    runs = [(POD, 0.0, 0.102, [(0.0005, 0.0005 + first)])]
    stripes = [(0.11 + 0.1 * s + 0.001 * s, 0.11 + 0.1 * (s + 1) + 0.001 * s) for s in range(4)]
    runs.append((POD, 0.109, 0.516, stripes))
    runs.append((POD, 0.52, 0.9, []))
    return runs


def test_readers_on_a_four_chip_pod_trace(tmp_path):
    trace = _trace(tmp_path, [_genesis_pod(c) for c in range(4)])
    # two runs a chip hold kernels: 1 stripe and 4
    assert read("pod_stripes_per_sweep", trace) == pytest.approx(2.5)
    # module time: 4 x (0.102 + 0.407); kernels: 0.04 + 3 x 0.1 + 4 x 0.4
    total = 4 * (0.102 + 0.407)
    kernel = 0.04 + 3 * 0.1 + 4 * 4 * 0.1
    assert read("pod_reduce_wait_pct", trace) == pytest.approx(100 * (total - kernel) / total)
    # full runs: 5 a chip, 4 on chip 0 (its 0.04 s run is cut short)
    assert read("pod_search_gnonce_per_s", trace) == pytest.approx(
        19 * SLAB / (19 * 0.1) / 1e9)


def test_readers_take_only_the_pod_program(tmp_path):
    """Another pod program on the same chips (the exact-min or MIN
    sweep, all named ``jit_per_device``) and kernels outside any run."""
    chips = []
    for c in range(4):
        runs = _genesis_pod(c)[:1]
        runs.append(("jit_per_device", 0.2, 0.5, [(0.21, 0.49)]))
        chips.append(runs)
    trace = _trace(tmp_path, chips)
    assert read("pod_stripes_per_sweep", trace) == 1.0
    assert read("pod_search_gnonce_per_s", trace) == pytest.approx(3 * SLAB / 0.3 / 1e9)


def test_readers_on_a_single_chip_genesis_trace(tmp_path):
    slabs = [(0.13 * i, 0.13 * i + 0.129) for i in range(6)]
    trace = _trace(tmp_path, [[("jit_pallas_search_candidates", s, e, [(s, e)])
                               for s, e in slabs]])
    assert trace.devices
    for name in READERS:
        assert read(name, trace) is None
        assert read(name, None) is None


def test_readers_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    (tmp_path / "window.json").write_text(json.dumps({"start": 0.0, "stop": 0.5}))
    trace = load_trace(str(tmp_path))
    assert not trace.devices
    for name in READERS:
        assert read(name, trace) is None
