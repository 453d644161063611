#!/usr/bin/env python3
"""The served mining path, measured from the client's side.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run: start a journaled coordinator and one device worker through
their command lines, read the device the worker's miner reports, warm up
with jobs of the cell's own shapes, then submit jobs over the
coordinator's client protocol in a closed loop for ``--seconds``, wait for
every job submitted in that time, stop every child, check the answers
against the configuration's plain reference, and print one JSON line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmark/configs/<name>.json``
with the reference ``<name>.py`` beside it, its traffic in
``benchmark/traffic/<name>.json``, each metric in
``benchmark/metrics/<name>.py``, and the device's peaks in
``benchmark/peaks.json``. This process never imports JAX: the worker's
miner child holds the chip, and the reference that needs one runs after
it has gone.

With ``--trace 1`` the miner child records a profiler trace of the first
seconds of the window and logs its compilations; the line then carries
the per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import shutil
import signal
import sys
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _path in (ROOT, HERE):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from cluster import DEVICE_LINE, Cluster, ClusterError  # noqa: E402
from traces import compile_events, load_trace, program_args  # noqa: E402
from traffic import Traffic  # noqa: E402

#: where runs keep their logs, journal and trace (listed in .gitignore)
RUNS_DIR = ".bench"
#: JAX's persistent compilation cache, at one fixed path in the checkout
CACHE_DIR = ".jax_cache"
#: how long after the window's end a job submitted inside it may answer
ANSWER_WAIT_S = 60.0


class RunError(Exception):
    """The run cannot give a result (no chip, a child that died)."""


def load_module(path: str):
    spec = importlib.util.spec_from_file_location(
        os.path.splitext(os.path.basename(path))[0].replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str = ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as fh:
            self.spec = json.load(fh)
        self.dir = os.path.join(root, self.spec["paths"][0])

    def cell(self, name: str) -> dict:
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        raise RunError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for cfg in self.spec["configs"]:
            if cfg["name"] == name:
                with open(os.path.join(self.root, cfg["file"])) as fh:
                    return json.load(fh)
        raise RunError(f"no config {name!r} in BENCHMARK.json")

    def reference(self, config: str):
        return load_module(os.path.join(self.dir, "configs", f"{config}.py"))

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.dir, "traffic", f"{name}.json")) as fh:
            return json.load(fh)

    def peaks(self, kind: str) -> dict:
        with open(os.path.join(self.dir, "peaks.json")) as fh:
            table = json.load(fh)["devices"]
        if kind not in table:
            raise RunError(f"no peaks for device kind {kind!r} in peaks.json")
        return table[kind]

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The metrics this cell reports in this kind of run."""
        group = self.spec["per_layer"] if trace else self.spec["end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def read_metric(self, name: str, run) -> Optional[float]:
        mod = load_module(os.path.join(self.dir, "metrics", f"{name}.py"))
        return mod.read(run)


def worker_argv(config: dict) -> List[str]:
    """The worker's options: the configuration's ``worker`` list, then
    each ``miner`` size as its flag (``roll_batch`` -> ``--roll-batch``),
    so the sizes the metrics count with are those the miner runs."""
    argv = list(config["worker"])
    for key, value in config.get("miner", {}).items():
        argv += [f"--{key.replace('_', '-')}", str(value)]
    return argv


def normalize(job: dict) -> dict:
    job = dict(job)
    if "span" in job:
        job["hi"] = job["lo"] + job.pop("span") - 1
    return job


def to_request(job: dict, job_id: int):
    from tpuminter import chain
    from tpuminter.protocol import PowMode, Request

    rolled = {}
    if job["kind"] == "rolled":
        rolled = dict(
            coinbase_prefix=bytes.fromhex(job["prefix"]),
            coinbase_suffix=bytes.fromhex(job["suffix"]),
            extranonce_size=job["extranonce_size"],
            branch=tuple(bytes.fromhex(s) for s in job["branch"]),
        )
    mode = PowMode.SCRYPT if job["kind"] == "scrypt" else PowMode.TARGET
    return Request(
        job_id=job_id, mode=mode, lower=job["lo"], upper=job["hi"],
        header=bytes.fromhex(job["header"]),
        target=chain.bits_to_target(int(job["bits"], 0)), **rolled,
    )


def useful_hashes(job: dict, answer: dict) -> int:
    """Hashes a job needed, from its answer: its range up to and
    including the winner, or all of it."""
    if answer["found"]:
        return answer["index"] - job["lo"] + 1
    return job["hi"] - job["lo"] + 1


class Run:
    """What one run recorded; the metric readers take it."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class Client:
    """One pool client: a single LSP connection to the coordinator that
    carries all of its jobs, one at a time (a new connection for each
    job can reuse a UDP port that the coordinator still holds for an
    earlier connection)."""

    def __init__(self, conn):
        self._conn = conn

    @classmethod
    async def connect(cls, port: int) -> "Client":
        from tpuminter.lsp import LspClient
        from tpuminter.lsp.params import FAST

        return cls(await LspClient.connect("127.0.0.1", port, FAST))

    async def job(self, job: dict, job_id: int, timeout: float) -> dict:
        """Submit one job and wait for its final answer."""
        return await asyncio.wait_for(self._job(job, job_id), timeout)

    async def _job(self, job: dict, job_id: int) -> dict:
        from tpuminter.protocol import Refuse, Result, decode_msg, encode_msg

        request = to_request(job, job_id)
        self._conn.write(encode_msg(request))
        while True:
            msg = decode_msg(await self._conn.read())
            if getattr(msg, "job_id", None) != job_id:
                continue
            if isinstance(msg, Result):
                return {"found": bool(msg.found), "index": int(msg.nonce),
                        "hash": int(msg.hash_value), "searched": int(msg.searched)}
            if isinstance(msg, Refuse):
                if msg.retry_after_ms <= 0:
                    raise RunError(f"the coordinator refused job {job_id}")
                await asyncio.sleep(msg.retry_after_ms / 1000.0)
                self._conn.write(encode_msg(request))

    async def close(self) -> None:
        await self._conn.close(drain_timeout=2.0)


async def drive(port: int, traffic: Traffic, seconds: float, t0: float,
                trace_dir: Optional[str]) -> tuple:
    """Warm-up, then the window: the closed loop of ``clients`` clients,
    each submitting its next job when its last one answers, until
    ``seconds`` have passed. Every job submitted in that time is waited
    for up to ANSWER_WAIT_S past it. Returns the window's records, the
    set-up time and the window's start on the wall clock."""
    mix = traffic.mix
    conns = [await Client.connect(port) for _ in range(mix.get("clients", 1))]
    try:
        for i, job in enumerate(traffic.warmup()):
            try:
                await conns[0].job(normalize(job), 1_000_000 + i, 600)
            except Exception as exc:
                raise RunError(f"warm-up job {i} got no answer: {exc!r}") from exc
        setup_s = time.monotonic() - t0
        window_wall = time.time()
        if trace_dir:
            _touch(os.path.join(trace_dir, "start"))
            await _await_file(os.path.join(trace_dir, "started"), 60)
        records: List[dict] = []
        start = time.monotonic()
        end = start + seconds
        if trace_dir:
            asyncio.get_running_loop().call_later(
                mix.get("trace_s", 5), _touch, os.path.join(trace_dir, "stop"))

        async def loop(client):
            while time.monotonic() < end:
                k = len(records)
                rec = {"k": k, "job": normalize(traffic.job(k)), "t_start": start,
                       "t_submit": time.monotonic(), "answer": None}
                records.append(rec)
                try:
                    rec["answer"] = await client.job(
                        rec["job"], k + 1, end + ANSWER_WAIT_S - time.monotonic())
                except Exception as exc:  # counted as unanswered
                    rec["error"] = repr(exc)
                rec["t_done"] = time.monotonic()

        await asyncio.gather(*(loop(c) for c in conns))
        return records, setup_s, window_wall
    finally:
        for c in conns:
            await c.close()


def verify(ref, traffic: Traffic, records: List[dict], workdir: str,
           env: dict) -> Dict[str, Dict[str, int]]:
    """The numbers compared, each with its limit (a number passes when
    it is at most its limit)."""
    answered = [r for r in records if r["answer"] is not None]
    for r in records:
        if r["answer"] is None:
            print(f"job {r['k']} got no answer: {r.get('error')}", file=sys.stderr)
    bad = 0
    for r in answered:
        reason = ref.check(r["job"], r["answer"])
        if reason is not None:
            bad += 1
            print(f"bad answer to job {r['k']}: {reason}", file=sys.stderr)
    picks = [answered[i] for i in traffic.sample(len(answered))]
    want = ref.expected([r["job"] for r in picks], workdir, env) if picks else []
    mismatched = 0
    for r, w in zip(picks, want):
        got = r["answer"]
        # a reference that gives no index for a range without a winner
        # leaves the rest of that answer unchecked
        if any(got[key] != w[key] for key in ("found", "index", "hash") if key in w):
            mismatched += 1
            print(f"job {r['k']}: answer {got} but the reference gives {w}",
                  file=sys.stderr)
    return {
        "unanswered": {"value": len(records) - len(answered), "limit": 0},
        "bad_answers": {"value": bad, "limit": 0},
        "sample_mismatches": {"value": mismatched, "limit": 0},
        "unsampled": {"value": 0 if picks else 1, "limit": 0},
    }


def child_env(trace_dir: Optional[str], out_dir: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    env["PYTHONUNBUFFERED"] = "1"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, CACHE_DIR)
    env["TPUMINTER_BENCH_OUT"] = out_dir
    env.pop("TPUMINTER_BENCH_TRACE", None)
    env.pop("JAX_LOG_COMPILES", None)
    if trace_dir is not None:
        env["TPUMINTER_BENCH_TRACE"] = trace_dir
        env["JAX_LOG_COMPILES"] = "1"
    return env


def _touch(path: str) -> None:
    with open(path, "w"):
        pass


async def _await_file(path: str, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise RunError(f"{os.path.basename(path)} did not appear in {timeout} s")
        await asyncio.sleep(0.05)


def find_device(cluster: Cluster, cell: dict, bench: Bench) -> tuple:
    """The device the worker's miner reports, and its peaks; a miner
    that is not on as many accelerator chips as the cell asks for is an
    error."""
    m = cluster.wait_for("worker", DEVICE_LINE, 600)
    device = {"platform": m[1], "kind": m[2], "count": int(m[3])}
    if device["platform"] == "cpu" or device["count"] < cell["chips"]:
        raise RunError(f"the worker's miner is on {device}, not on "
                       f"{cell['chips']} accelerator chip(s)")
    return device, bench.peaks(device["kind"])


def read_memory(out_dir: str) -> dict:
    """What the miner's probe recorded of device memory."""
    path = os.path.join(out_dir, "memory.json")
    if not os.path.exists(path):
        raise RunError("the miner recorded no device memory")
    with open(path) as fh:
        return json.load(fh)


def miner_pauses(out_dir: str, window_wall: float) -> str:
    """The longest gap between the miner's records in the window, and
    the compiles and full collections it recorded there (rows of
    ``steps.jsonl``, written by the probe in ``worker_main.py``)."""
    path = os.path.join(out_dir, "steps.jsonl")
    if not os.path.exists(path):
        return "the miner recorded no steps"
    with open(path) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    rows = [r for r in rows if r[0] >= window_wall]
    steps = [r for r in rows if r[1] in ("start", "step", "result")]
    gaps = [(b[0] - a[0], a[1], b[1]) for a, b in zip(steps, steps[1:])]
    gap = max(gaps, default=(0.0, "-", "-"))
    events = [r[1:] for r in rows if r[1] in ("jax", "gc")]
    return (f"miner in the window: longest gap {gap[0]:.3f} s ({gap[1]} -> {gap[2]}); "
            f"{len(events)} compiles or full collections of 10 ms or more {events[:3]}")


def measure(bench: Bench, workload: str, seed: int, seconds: float,
            trace: bool, launcher: Optional[str] = None) -> dict:
    """One run of one cell; returns the result line's object."""
    t0 = time.monotonic()
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    ref = bench.reference(cell["config"])
    traffic = Traffic(bench.traffic(cell["traffic"]), seed)
    out_dir = os.path.join(bench.root, RUNS_DIR, workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    trace_dir = os.path.join(out_dir, "trace") if trace else None
    if trace_dir:
        os.makedirs(trace_dir)
    env = child_env(trace_dir, out_dir)
    launcher = launcher or os.path.join(HERE, "worker_main.py")
    cluster = Cluster(out_dir, [sys.executable, launcher, *worker_argv(config)], env)
    try:
        device, peaks = find_device(cluster, cell, bench)
        records, setup_s, window_wall = asyncio.run(drive(
            cluster.port, traffic, seconds, t0, trace_dir))
        _note(f"set-up {setup_s:.1f} s; window: {len(records)} jobs, "
              f"ended {time.monotonic() - t0 - setup_s:.1f} s after set-up")
        if trace_dir:
            _touch(os.path.join(trace_dir, "stop"))
            asyncio.run(_await_file(os.path.join(trace_dir, "window.json"), 300))
        cluster.check_alive()
    except ClusterError as exc:
        raise RunError(str(exc)) from exc
    finally:
        cluster.stop()
    memory = read_memory(out_dir)
    device["memory_peak_bytes"] = memory["peak_bytes"]
    _note(f"device memory: {json.dumps(memory)}")
    with open(os.path.join(out_dir, "jobs.jsonl"), "w") as fh:
        # wall-clock times, to line up with what the miner recorded
        for r in records:
            row = {key: r.get(key) for key in ("k", "answer", "error")}
            for key in ("t_submit", "t_done"):
                row[key] = window_wall + r[key] - r["t_start"] if key in r else None
            fh.write(json.dumps(row) + "\n")

    _note(miner_pauses(out_dir, window_wall))
    _note(f"children stopped at {time.monotonic() - t0:.1f} s")
    checks = verify(ref, traffic, records, out_dir, child_env(None, out_dir))
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    _note(f"answers checked at {time.monotonic() - t0:.1f} s")
    for r in records:
        if r["answer"] is not None:
            r["useful"] = useful_hashes(r["job"], r["answer"])

    run = Run(
        records=records, setup_s=setup_s, chips=cell["chips"],
        window_wall=window_wall, config=config, peaks=peaks, trace=None,
        compiles=[], program_args={},
    )
    out = {"correct": correct, "attempted": len(records),
           "failed": checks["unanswered"]["value"] + checks["bad_answers"]["value"],
           "metrics": {}, "device": device}
    if trace_dir:
        run.trace = load_trace(trace_dir)
        with open(cluster.log_path("worker"), errors="replace") as fh:
            log = fh.read()
        run.compiles = compile_events(log)
        run.program_args = program_args(log)
        if run.trace is not None and run.trace.busy_s() is not None:
            device["busy_s"] = run.trace.busy_s()
            device["window_s"] = run.trace.window_s
            out["breakdown"] = {"device_ops": run.trace.top_programs(),
                                "idle_gaps": run.trace.idle_gaps()}
    for metric in bench.metrics(workload, trace_dir is not None):
        value = bench.read_metric(metric["name"], run)
        if value is not None:
            out["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
    out["checks"] = checks
    return out


def _note(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def main(argv=None, bench: Optional[Bench] = None, **measure_kw) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    # a SIGTERM (a caller's time limit) unwinds through measure()'s
    # cleanup, so no coordinator, worker or miner outlives the run
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    if not os.path.isdir(os.path.join(ROOT, "tpuminter")):
        print("benchmark: no tpuminter package beside benchmark/", file=sys.stderr)
        return 2
    try:
        out = measure(bench or Bench(), args.workload, args.seed, args.seconds,
                      bool(args.trace), **measure_kw)
    except (RunError, ClusterError) as exc:
        print(f"benchmark: no result: {exc}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} <= {c['limit']}", file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
