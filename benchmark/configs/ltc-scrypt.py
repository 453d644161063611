"""Plain reference for Litecoin proof of work: ``scrypt(header80,
salt=header80, N=1024, r=1, p=1, dkLen=32)`` (RFC 7914), compared with a
compact-bits target as a little-endian number.

It imports nothing of the system it checks: ``hashlib.scrypt`` on the
host. A job's whole range is recomputed in a process of its own
(``python ltc-scrypt.py IN OUT``) spread over the host's cores.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from typing import List, Optional

N, R, P, DKLEN = 1024, 1, 1, 32


def bits_to_target(bits: int) -> int:
    return (bits & 0x7FFFFF) << (8 * ((bits >> 24) - 3))


def target_of(job: dict) -> int:
    return bits_to_target(int(job["bits"], 0))


def hash_value(header80: bytes) -> int:
    d = hashlib.scrypt(header80, salt=header80, n=N, r=R, p=P, dklen=DKLEN)
    return int.from_bytes(d, "little")


def header_at(job: dict, nonce: int) -> bytes:
    return bytes.fromhex(job["header"])[:76] + nonce.to_bytes(4, "little")


def check(job: dict, answer: dict) -> Optional[str]:
    """What is wrong with one answer that the host can see at once: its
    hash re-computed, and on the right side of the target."""
    if not job["lo"] <= answer["index"] <= job["hi"]:
        return f"index {answer['index']} outside [{job['lo']}, {job['hi']}]"
    h = hash_value(header_at(job, answer["index"]))
    if h != answer["hash"]:
        return f"index {answer['index']} hashes to {h:064x}, not {answer['hash']:064x}"
    if answer["found"] != (h <= target_of(job)):
        return f"index {answer['index']}: found={answer['found']} disagrees with the target"
    return None


def _scan(header_hex: str, lo: int, hi: int, target: int):
    """(first index meeting the target or None, (min hash, its index))."""
    header76 = bytes.fromhex(header_hex)[:76]
    best = None
    for nonce in range(lo, hi + 1):
        h = hash_value(header76 + nonce.to_bytes(4, "little"))
        if h <= target:
            return nonce, (h, nonce)
        if best is None or h < best[0]:
            best = (h, nonce)
    return None, best


def _job_answer(pool, job: dict, parts: int) -> dict:
    lo, hi, target = job["lo"], job["hi"], target_of(job)
    step = -(-(hi - lo + 1) // parts)
    spans = [(s, min(s + step - 1, hi)) for s in range(lo, hi + 1, step)]
    results = list(pool.map(_scan, [job["header"]] * len(spans),
                            [s for s, _ in spans], [e for _, e in spans],
                            [target] * len(spans)))
    for first, _ in results:
        if first is not None:  # spans are in order: the first is first
            return {"found": True, "index": first,
                    "hash": hash_value(header_at(job, first))}
    h, nonce = min(best for _, best in results)
    return {"found": False, "index": nonce, "hash": h}


def expected(jobs: List[dict], workdir: str, env: dict) -> List[dict]:
    """The answer each job must get: the first nonce that meets the
    target, or else the range's least hash and its nonce."""
    src = os.path.join(workdir, "reference_in.json")
    dst = os.path.join(workdir, "reference_out.json")
    with open(src, "w") as fh:
        json.dump(jobs, fh)
    subprocess.run([sys.executable, __file__, src, dst], env=env,
                   check=True, timeout=600)
    with open(dst) as fh:
        return json.load(fh)


def _main(src: str, dst: str) -> None:
    with open(src) as fh:
        jobs = json.load(fh)
    cores = os.cpu_count() or 1
    with ProcessPoolExecutor(cores, mp_context=get_context("spawn")) as pool:
        out = [_job_answer(pool, job, 4 * cores) for job in jobs]
    with open(dst, "w") as fh:
        json.dump(out, fh)


if __name__ == "__main__":
    _main(sys.argv[1], sys.argv[2])
