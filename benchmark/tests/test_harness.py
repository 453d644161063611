"""The harness on the CPU, with ``--backend cpu`` workers and traffic cut
to sizes a host mines in seconds: jobs from seeds, discovery of cells,
configurations, mixes and metrics by name, the refusal of a miner that
is not on an accelerator, and the check seeing each planted fault."""

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, BENCH)
import run  # noqa: E402
from traffic import Traffic  # noqa: E402

GENESIS_NONCE = 2083236893
FAULT_WORKER = os.path.join(BENCH, "tests", "fault_worker.py")
MIXES = ["genesis_diff1", "rolled_share", "scrypt_sweep"]


def _small(mix: str, spec: dict) -> dict:
    """The mix at a size a host mines in a few milliseconds a job."""
    if mix == "genesis_diff1":
        spec["per_job"]["lo"] = {"uniform": [GENESIS_NONCE - 3000, GENESIS_NONCE]}
    elif mix == "rolled_share":
        spec["per_run"]["bits"] = "0x2000ffff"
        spec["per_job"] = {"lo": {"step": [0, 1 << 32]}, "span": 3000}
    elif mix == "scrypt_sweep":
        spec["per_job"]["span"] = 16
    return spec


def _set_worker(bench_root, *argv):
    """Every configuration's worker command line, with no miner sizes."""
    for path in (bench_root / "benchmark" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        path.write_text(json.dumps(dict(cfg, worker=list(argv), miner={})))


@pytest.fixture
def small_bench(tmp_path, monkeypatch):
    """A copy of the benchmark whose traffic is cut to CPU sizes, with
    the rolled-share cell that the chip's benchmark leaves out, run by
    ``--backend cpu`` workers: the device is taken as found, and their
    miner, in the worker's own process, records no device memory."""
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    spec["workloads"].append({"name": "btc.rolled.share", "config": "btc-sha256d",
                              "traffic": "rolled_share", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for mix in MIXES:
        path = tmp_path / "benchmark" / "traffic" / f"{mix}.json"
        path.write_text(json.dumps(_small(mix, json.loads(path.read_text()))))
    _set_worker(tmp_path, "--backend", "cpu")
    monkeypatch.setattr(run, "find_device", lambda *a: (
        {"platform": "cpu", "kind": "cpu", "count": 1}, {}))
    monkeypatch.setattr(run, "read_memory", lambda out_dir: {"peak_bytes": 0})
    return tmp_path


def _run(bench_root, cell, seconds=2, **kw):
    """main() on the CPU; returns (exit code, parsed last line or None)."""
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", "4294967396",
                       "--seconds", str(seconds), "--trace", "0"],
                      bench=run.Bench(str(bench_root)), **kw)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("mix", MIXES)
def test_jobs_follow_the_seed(mix):
    spec = run.Bench().traffic(mix)
    a, b, c = Traffic(spec, 2**31 + 5), Traffic(spec, 2**31 + 5), Traffic(spec, 2**31 + 6)
    jobs = lambda t: [t.job(k) for k in range(5)] + t.warmup()  # noqa: E731
    assert jobs(a) == jobs(b)
    assert jobs(a) != jobs(c)
    assert a.sample(40) == b.sample(40)


@pytest.mark.parametrize("cell,clients", [
    ("btc.diff1.genesis", 1), ("btc.rolled.share", 1), ("ltc.scrypt.sweep", 1),
    ("btc.diff1.genesis", 2),
])
def test_sound_runs_are_correct(small_bench, cell, clients):
    for path in (small_bench / "benchmark" / "traffic").glob("*.json"):
        path.write_text(json.dumps(dict(json.loads(path.read_text()), clients=clients)))
    rc, out = _run(small_bench, cell)
    assert rc == 0 and out["correct"], out
    assert out["attempted"] > 1 and out["failed"] == 0
    assert out["metrics"]["hashrate_per_chip"]["value"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell,fault", [
    ("btc.diff1.genesis", "flip_nonce"),
    ("btc.diff1.genesis", "skip_half"),
    ("btc.diff1.genesis", "no_search"),
    ("btc.rolled.share", "flip_nonce"),
    ("btc.rolled.share", "skip_half"),
    ("btc.rolled.share", "no_search"),
    ("ltc.scrypt.sweep", "flip_nonce"),
    ("ltc.scrypt.sweep", "skip_half"),
    ("ltc.scrypt.sweep", "no_search"),
])
def test_planted_faults_are_not_correct(small_bench, cell, fault, monkeypatch):
    monkeypatch.setenv("TPUMINTER_BENCH_FAULT", fault)
    monkeypatch.setattr(run, "ANSWER_WAIT_S", 5.0)
    for mix in MIXES:
        # the fault meets the window's jobs, every answer is re-derived,
        # so one skipped winner is seen, and a job that never answers is
        # given up on soon
        path = small_bench / "benchmark" / "traffic" / f"{mix}.json"
        spec = json.loads(path.read_text())
        path.write_text(json.dumps(dict(spec, warmup=[], sample="all")))
    rc, out = _run(small_bench, cell, seconds=2, launcher=FAULT_WORKER)
    assert rc != 0 or not out["correct"], out


def test_a_miner_on_the_cpu_gives_no_result(small_bench, monkeypatch):
    monkeypatch.undo()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    _set_worker(small_bench, "--backend", "jax")
    rc, out = _run(small_bench, "btc.diff1.genesis")
    assert rc != 0 and out is None


def test_no_program_no_result(tmp_path, capsys):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    import subprocess

    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "btc.diff1.genesis",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_new_files_are_found_by_name(small_bench):
    """A configuration, a mix and a metric added as files plus entries,
    with no existing file edited, run as a cell."""
    bench = small_bench / "benchmark"
    shutil.copy(bench / "configs" / "btc-sha256d.py", bench / "configs" / "toy-sha256d.py")
    cfg = json.loads((bench / "configs" / "btc-sha256d.json").read_text())
    (bench / "configs" / "toy-sha256d.json").write_text(json.dumps(dict(cfg, name="toy-sha256d")))
    mix = json.loads((bench / "traffic" / "genesis_diff1.json").read_text())
    mix["per_job"]["lo"] = {"uniform": [GENESIS_NONCE - 100, GENESIS_NONCE]}
    (bench / "traffic" / "near_genesis.json").write_text(json.dumps(mix))
    (bench / "metrics" / "jobs_answered.py").write_text(
        "def read(run):\n    return sum(r['answer'] is not None for r in run.records)\n")
    spec = json.loads((small_bench / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="toy-sha256d",
                                file="benchmark/configs/toy-sha256d.json"))
    spec["workloads"].append({"name": "toy.near", "config": "toy-sha256d",
                              "traffic": "near_genesis", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "jobs_answered", "unit": "count", "better": "higher",
                               "bound": 0.1, "source": "host_clock", "workloads": ["toy.near"]})
    (small_bench / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, out = _run(small_bench, "toy.near")
    assert rc == 0 and out["correct"]
    assert out["metrics"]["jobs_answered"]["value"] == out["attempted"]
    rc, out = _run(small_bench, "btc.diff1.genesis")
    assert "jobs_answered" not in out["metrics"]
