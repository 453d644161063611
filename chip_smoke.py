#!/usr/bin/env python
"""Chip smoke: mine real jobs on the TPU through the user's entry points.

Starts ``python -m tpuminter.coordinator`` and one
``python -m tpuminter.worker --backend tpu`` as child processes, drives
jobs through ``python -m tpuminter.client`` and checks every answer
against a host-side recomputation with ``hashlib``:

- TARGET: the genesis header at bits 0x1d00ffff over nonces 0 .. 2^32-1
  (the genesis nonce is the first winner in that range);
- rolled: a coinbase with a 4-byte extranonce under a 12-level merkle
  branch made from ``--seed``, extranonces 0 .. 16 at bits 0x1d00ffff;
- SCRYPT: 4096 nonces of the genesis header, target the window minimum;
- hashcore fmin: 2^20 indices on device lanes.

``--chips 4`` runs only the multi-chip path instead: one
``--backend pod`` worker whose mesh spans every chip, the genesis TARGET
job and a MIN toy job over 2^30 nonces (two chunks of one pod span at
the default slab).

The parent imports JAX only after every child has exited (a chip
belongs to one process). The last line of standard output is
``{"ok": true, "device": {...}}``; any failed check, child that dies, or
device that is not a TPU ends the run with a non-zero exit code and no
such line.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

GENESIS = bytes.fromhex(
    "01000000" + "00" * 32
    + "3ba3edfd7a7b12b27ac72c3e67768f617fc81bc3888a51323a9fb8aa4b1e5e4a"
    + "29ab5f49" + "ffff001d" + "1dac2b7c"
)
GENESIS_NONCE = 2083236893
GENESIS_HASH_HEX = (
    "000000000019d6689c085ae165831e934ff763ae46a2a6c172b3f1b60a8ce26f"
)
DIFF1_BITS = 0x1D00FFFF
#: nonces in the four-chip MIN job: two chunks of one pod span each
#: (4 chips x the default 2^27 slab), so every nonce rides the sharded
#: pod step and none the single-chip tail kernel
POD_MIN_NONCES = 1 << 30


class SmokeError(Exception):
    pass


def dsha256(b: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(b).digest()).digest()


def bits_to_target(bits: int) -> int:
    return (bits & 0xFFFFFF) << (8 * ((bits >> 24) - 3))


def covering_bits(value: int) -> int:
    """The smallest compact-bits target that is >= ``value``."""
    exp = (value.bit_length() + 7) // 8
    while True:
        unit = 256 ** (exp - 3)
        mant = -(-value // unit)
        if mant < 0x800000:
            return (exp << 24) | mant
        exp += 1


def with_nonce(header80: bytes, nonce: int) -> bytes:
    return header80[:76] + nonce.to_bytes(4, "little")


class Cluster:
    """Coordinator + one worker as child processes, logs under OUT."""

    def __init__(self, worker_args):
        os.makedirs(OUT, exist_ok=True)
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        journal = os.path.join(OUT, "journal.wal")
        if os.path.exists(journal):
            os.remove(journal)
        self.env = dict(os.environ, PYTHONUNBUFFERED="1")
        self.procs = {}
        self._spawn("coordinator", [str(self.port), "--journal", journal])
        self.wait_for("coordinator", r"coordinator listening on port", 60)
        self._spawn("worker", [f"127.0.0.1:{self.port}", *worker_args])

    def _spawn(self, role, args):
        # a session of its own: stopping the role's process group also
        # reaches the device miner that the worker spawns
        log = open(os.path.join(OUT, f"{role}.log"), "w")
        self.procs[role] = (subprocess.Popen(
            [sys.executable, "-m", f"tpuminter.{role}", *args],
            cwd=ROOT, env=self.env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        ), log)

    def log(self, role) -> str:
        with open(os.path.join(OUT, f"{role}.log")) as fh:
            return fh.read()

    def check_alive(self):
        for role, (proc, _) in self.procs.items():
            if proc.poll() is not None:
                raise SmokeError(
                    f"{role} exited with code {proc.returncode}:\n"
                    + self.log(role)[-3000:]
                )

    def wait_for(self, role: str, pattern: str, timeout: float) -> str:
        """Block until ``role``'s log matches ``pattern``; return the
        match."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            self.check_alive()
            m = re.search(pattern, self.log(role))
            if m:
                return m.group(0)
            time.sleep(0.2)
        raise SmokeError(f"{role} did not log {pattern!r} in {timeout} s")

    def client(self, args, timeout: float) -> str:
        """Run one client job; return its stdout."""
        proc = subprocess.Popen(
            [sys.executable, "-m", "tpuminter.client",
             f"127.0.0.1:{self.port}", *args, "--timeout", str(timeout)],
            cwd=ROOT, env=self.env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        try:
            while proc.poll() is None:
                self.check_alive()
                time.sleep(0.2)
        finally:
            if proc.poll() is None:
                proc.kill()
        out, err = proc.communicate()
        if proc.returncode != 0 or "Result" not in out:
            raise SmokeError(
                f"client {args[:2]} failed (code {proc.returncode}):\n"
                f"{out}\n{err[-2000:]}"
            )
        return out

    def stop(self):
        """Stop every child, the worker first with SIGINT (it shuts its
        device miner down cleanly), and wait until no process of either
        group is left: the chip is free only once the miner child has
        exited. The worker and coordinator must still have been running
        (a child that died earlier is a failure)."""
        died = [r for r, (p, _) in self.procs.items() if p.poll() is not None]
        for role in ("worker", "coordinator"):
            if role not in self.procs:
                continue
            proc, log = self.procs[role]
            if proc.poll() is None:
                proc.send_signal(signal.SIGINT)
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            _reap_group(proc.pid)
            log.close()
        if died:
            raise SmokeError(f"{', '.join(died)} exited before the end")


def _reap_group(pgid: int, timeout: float = 30.0) -> None:
    """Signal whatever is left of process group ``pgid`` (SIGTERM, then
    SIGKILL after ``timeout``) until the group is empty."""
    start = time.time()
    sig = signal.SIGTERM
    while True:
        try:
            os.killpg(pgid, sig)
        except (ProcessLookupError, PermissionError):
            return
        if time.time() - start > 2 * timeout:
            raise SmokeError(f"process group {pgid} did not end")
        if time.time() - start > timeout:
            sig = signal.SIGKILL
        time.sleep(0.2)


def _result_line(out: str) -> str:
    return next(l for l in out.splitlines() if l.startswith("Result"))


def job_target(cl: Cluster) -> str:
    out = cl.client(["--header", GENESIS.hex(), "--bits", hex(DIFF1_BITS)],
                    timeout=400)
    line = _result_line(out)
    want = f"Result {GENESIS_HASH_HEX} {GENESIS_NONCE}"
    h = dsha256(with_nonce(GENESIS, GENESIS_NONCE))
    if h[::-1].hex() != GENESIS_HASH_HEX or line != want:
        raise SmokeError(f"TARGET: got {line!r}, want {want!r}")
    return f"nonce={GENESIS_NONCE} hash={GENESIS_HASH_HEX}"


def job_rolled(cl: Cluster, seed: int) -> str:
    rng = random.Random(seed)
    prefix, suffix = rng.randbytes(42), rng.randbytes(70)
    branch = [rng.randbytes(32) for _ in range(12)]
    args = ["--header", GENESIS.hex(), "--bits", hex(DIFF1_BITS),
            "--coinbase-prefix", prefix.hex(), "--coinbase-suffix",
            suffix.hex(), "--extranonce-size", "4", "--max-extranonce", "16"]
    for sib in branch:
        args += ["--branch", sib.hex()]
    line = _result_line(cl.client(args, timeout=500))
    m = re.fullmatch(r"Result ([0-9a-f]{64}) extranonce=(\d+) nonce=(\d+)",
                     line)
    if not m:
        raise SmokeError(f"rolled: unexpected answer {line!r}")
    en, nonce = int(m.group(2)), int(m.group(3))
    node = dsha256(prefix + en.to_bytes(4, "little") + suffix)
    for sib in branch:
        node = dsha256(node + sib)
    header = GENESIS[:36] + node + GENESIS[68:76] + nonce.to_bytes(4, "little")
    h = dsha256(header)
    if not en <= 16 or h[::-1].hex() != m.group(1):
        raise SmokeError(f"rolled: {line!r} does not rehash to its claim")
    if int.from_bytes(h, "little") > bits_to_target(DIFF1_BITS):
        raise SmokeError(f"rolled: {line!r} misses the target")
    return f"extranonce={en} nonce={nonce} hash={m.group(1)}"


def scrypt_window(n: int):
    """Host truth for the SCRYPT job: sorted (hash value, nonce)."""
    vals = []
    for nonce in range(n):
        hdr = with_nonce(GENESIS, nonce)
        d = hashlib.scrypt(hdr, salt=hdr, n=1024, r=1, p=1, dklen=32)
        vals.append((int.from_bytes(d, "little"), nonce))
    return sorted(vals)


def job_scrypt(cl: Cluster, truth) -> str:
    (h_min, n_min), (h_next, _) = truth[0], truth[1]
    bits = covering_bits(h_min)
    if bits_to_target(bits) >= h_next:
        raise SmokeError("SCRYPT: no compact target isolates the minimum")
    line = _result_line(cl.client(
        ["--header", GENESIS.hex(), "--scrypt", "--bits", hex(bits),
         "--max-nonce", str(len(truth) - 1)], timeout=400))
    want_hex = h_min.to_bytes(32, "little")[::-1].hex()
    if line != f"Result {want_hex} {n_min}":
        raise SmokeError(f"SCRYPT: got {line!r}, want nonce {n_min} "
                         f"hash {want_hex}")
    return f"nonce={n_min} hash={want_hex}"


def splitmix_min(seed: int, n: int):
    """Host truth for hashcore fmin over [0, n): numpy u64 splitmix64."""
    import numpy as np

    idx = np.arange(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        z = np.uint64(seed) + (idx + np.uint64(1)) * np.uint64(
            0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    i = int(np.lexsort((idx, z))[0])
    return int(z[i]), i


def job_hashcore(cl: Cluster, seed: int) -> str:
    from tpuminter.workloads.hashcore import objective

    n = 1 << 20
    value, index = splitmix_min(seed, n)
    if objective(seed, index) != value:
        raise SmokeError("hashcore: numpy truth disagrees with objective()")
    out = cl.client(["--workload", "hashcore", "--variant", "fmin",
                     "--seed", str(seed), "--max-nonce", str(n - 1)],
                    timeout=400)
    want = f"Result [hashcore] fmin: value={value} index={index}"
    if _result_line(out) != want or f"searched={n}" not in out:
        raise SmokeError(f"hashcore: got {out!r}, want {want!r}")
    return f"value={value} index={index}"


def toy_min_truth(msg: str, n: int, lib_path=None):
    """Host truth for the MIN toy job over [0, n): (value, nonce) from
    the native C++ scan on every core, the winner rehashed with
    hashlib."""
    import ctypes

    from tpuminter.native_worker import load_native_lib

    lib = load_native_lib(lib_path)
    data = msg.encode()
    parts = 4 * (os.cpu_count() or 1)
    step = -(-n // parts)

    def scan(lo):
        nonce, fold = ctypes.c_uint64(), ctypes.c_uint64()
        lib.toy_min_search(data, len(data), lo, min(lo + step, n) - 1,
                           ctypes.byref(nonce), ctypes.byref(fold))
        return fold.value, nonce.value

    with ThreadPoolExecutor(os.cpu_count()) as pool:
        value, nonce = min(pool.map(scan, range(0, n, step)))
    digest = hashlib.sha256(data + nonce.to_bytes(8, "big")).digest()
    if int.from_bytes(digest[:8], "big") != value:
        raise SmokeError("MIN: the native scan disagrees with hashlib")
    return value, nonce


def job_toy_min(cl: Cluster, msg: str, truth) -> str:
    line = _result_line(cl.client([msg, str(POD_MIN_NONCES - 1)],
                                  timeout=400))
    want = truth.result()
    if line != f"Result {want[0]} {want[1]}":
        raise SmokeError(f"MIN: got {line!r}, want {want}")
    return f"value={want[0]} nonce={want[1]}"


def run_phase(name, fn, *args):
    t0 = time.time()
    detail = fn(*args)
    print(f"phase={name} ok wall_s={time.time() - t0:.3f} {detail}",
          flush=True)


def smoke(chips: int, seed: int) -> None:
    make = subprocess.run(["make", "-B", "-C", "native"], cwd=ROOT,
                          capture_output=True, text=True)
    if make.returncode != 0:
        raise SmokeError(f"native build failed:\n{make.stderr[-2000:]}")
    if chips == 1:
        worker = ["--backend", "tpu", "--dev-lanes", "on"]
    else:
        worker = ["--backend", "pod"]
    cl = Cluster(worker)
    try:
        t0 = time.time()
        if chips == 1:
            scrypt_truth = scrypt_window(4096)
        else:
            msg = f"chip-smoke-{seed}"
            pool = ThreadPoolExecutor(1)
            min_truth = pool.submit(toy_min_truth, msg, POD_MIN_NONCES)
            pool.shutdown(wait=False)
        device = cl.wait_for(
            "worker", r"device: platform=\S+ kind=.* count=\d+", 300
        )
        print(f"phase=worker_start ok wall_s={time.time() - t0:.3f} {device}",
              flush=True)
        if "platform=tpu " not in device:
            raise SmokeError(f"worker is not on a TPU: {device}")
        if chips == 1:
            run_phase("target", job_target, cl)
            run_phase("rolled", job_rolled, cl, seed)
            run_phase("scrypt", job_scrypt, cl, scrypt_truth)
            run_phase("hashcore", job_hashcore, cl, seed)
        else:
            mesh = cl.wait_for("worker", r"pod mesh: \d+ devices", 60)
            if mesh != f"pod mesh: {chips} devices":
                raise SmokeError(f"pod mesh is not {chips} devices: {mesh}")
            print(f"phase=pod_mesh ok {mesh}", flush=True)
            run_phase("pod_target", job_target, cl)
            run_phase("pod_min", job_toy_min, cl, msg, min_truth)
    except BaseException:
        try:
            cl.stop()
        except SmokeError:
            pass
        raise
    cl.stop()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    # a SIGTERM (a caller's time limit) unwinds through smoke()'s
    # cleanup, so no coordinator or worker outlives the script
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    if not os.path.isdir(os.path.join(ROOT, "tpuminter")):
        print("chip_smoke: no tpuminter package next to this script",
              file=sys.stderr)
        return 2
    try:
        smoke(args.chips, args.seed)
    except SmokeError as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    import jax  # only now: every child has exited

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" or dev["count"] < args.chips:
        print(f"chip_smoke: FAILED: device {dev}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
