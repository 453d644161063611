"""Plain reference for Bitcoin proof of work mined by one four-chip pod
worker: double SHA-256 of the 80-byte block header, a 2^32 nonce field,
compact-bits targets, and the Stratum V1 coinbase (``coinb1 |
extranonce2 | coinb2``) folded up a merkle branch to the header's merkle
root. The pod shards each chunk over its chips, but owes the same
answers as one chip: the first share of each range.

It imports nothing of the system it checks. Host work uses ``hashlib``.
The first share in a range of up to 2^34 indices is too much for a host,
so :func:`expected` sweeps for it on the device with the plain
``jax.numpy`` SHA-256 below, in a process of its own (``python
btc-sha256d-pod4.py IN OUT``), on one chip after the worker has gone,
and re-hashes every share it finds with ``hashlib``. Nothing is taken as
known: not even block 0's nonce.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
from collections import deque
from typing import List, Optional

MASK = 0xFFFFFFFF
NONCE_BITS = 32

K = [
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
    0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
    0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
    0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
    0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
]
IV = [
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
]


def dsha256(data: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


def bits_to_target(bits: int) -> int:
    return (bits & 0x7FFFFF) << (8 * ((bits >> 24) - 3))


def target_of(job: dict) -> int:
    return bits_to_target(int(job["bits"], 0))


def header_at(job: dict, index: int) -> bytes:
    """The 80-byte header that global index ``index`` of ``job`` hashes."""
    header = bytes.fromhex(job["header"])
    if job["kind"] == "rolled":
        en, nonce = index >> NONCE_BITS, index & ((1 << NONCE_BITS) - 1)
        coinbase = (bytes.fromhex(job["prefix"])
                    + en.to_bytes(job["extranonce_size"], "little")
                    + bytes.fromhex(job["suffix"]))
        node = dsha256(coinbase)
        for sibling in job["branch"]:
            node = dsha256(node + bytes.fromhex(sibling))
        header = header[:36] + node + header[68:]
    else:
        nonce = index
    return header[:76] + nonce.to_bytes(4, "little")


def hash_value(header80: bytes) -> int:
    """The header's hash as the little-endian number compared with the
    target."""
    return int.from_bytes(dsha256(header80), "little")


def check(job: dict, answer: dict) -> Optional[str]:
    """What is wrong with one answer that the host can see at once (a
    winner re-hashed through coinbase, branch and header); None if
    nothing is."""
    if not job["lo"] <= answer["index"] <= job["hi"]:
        return f"index {answer['index']} outside [{job['lo']}, {job['hi']}]"
    if not answer["found"]:
        return None
    h = hash_value(header_at(job, answer["index"]))
    if h != answer["hash"]:
        return f"index {answer['index']} hashes to {h:064x}, not {answer['hash']:064x}"
    if h > target_of(job):
        return f"index {answer['index']} misses the target"
    return None


def _segments(job: dict) -> List[list]:
    """[header76 hex, global base, first nonce, last nonce] for each
    2^32-nonce segment of the job's range, in index order."""
    out = []
    lo, hi = job["lo"], job["hi"]
    while lo <= hi:
        base = lo >> NONCE_BITS << NONCE_BITS
        last = min(hi, base + (1 << NONCE_BITS) - 1)
        out.append([header_at(job, lo)[:76].hex(), base, lo - base, last - base])
        lo = last + 1
    return out


def expected(jobs: List[dict], workdir: str, env: dict) -> List[dict]:
    """The answer each job must get: its first share, or not found."""
    src = os.path.join(workdir, "reference_in.json")
    dst = os.path.join(workdir, "reference_out.json")
    with open(src, "w") as fh:
        json.dump(jobs, fh)
    subprocess.run([sys.executable, __file__, src, dst], env=env,
                   check=True, timeout=600)
    with open(dst) as fh:
        firsts = json.load(fh)
    return [{"found": False} if first is None else
            {"found": True, "index": first, "hash": hash_value(header_at(job, first))}
            for job, first in zip(jobs, firsts)]


# -- the device sweep (runs in its own process) -----------------------------

def compress_host(state, block: bytes):
    """One SHA-256 compression on Python ints (for the midstate)."""
    w = [int.from_bytes(block[4 * i:4 * i + 4], "big") for i in range(16)]
    return _compress(state, w, lambda x: x & MASK, lambda x: (~x) & MASK, int)


def _compress(state, w, wrap, inv, const):
    def rotr(x, n):
        return wrap((x >> n) | (x << (32 - n)))

    w = list(w)
    for i in range(16, 64):
        s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3)
        s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10)
        w.append(wrap(w[i - 16] + s0 + w[i - 7] + s1))
    a, b, c, d, e, f, g, h = state
    for i in range(64):
        s1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25)
        ch = (e & f) ^ (inv(e) & g)
        t1 = wrap(h + s1 + ch + const(K[i]) + w[i])
        s0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = wrap(s0 + maj)
        a, b, c, d, e, f, g, h = wrap(t1 + t2), a, b, c, wrap(d + t1), e, f, g
    return [wrap(s + x) for s, x in zip(state, (a, b, c, d, e, f, g, h))]


def make_sweep(tile: int):
    """``sweep(mid, tail, target, base) -> offset``: the first offset in
    [0, tile) whose nonce ``base + offset`` gives a double SHA-256 that,
    read as a little-endian number, is at most ``target`` (eight 32-bit
    words, most significant first), or ``tile``. ``mid`` is the
    midstate of the header's first 64 bytes, ``tail`` its bytes 64..75
    as three big-endian words."""
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32

    def wrap(x):
        return x

    def bswap(x):
        return ((x << 24) | ((x & u32(0xFF00)) << 8)
                | ((x >> 8) & u32(0xFF00)) | (x >> 24))

    @jax.jit
    def sweep(mid, tail, target, base):
        offs = jax.lax.iota(u32, tile).reshape(tile // 128, 128)
        nonce = base + offs
        zero = jnp.zeros_like(nonce)
        w = [tail[0] + zero, tail[1] + zero, tail[2] + zero, bswap(nonce),
             u32(0x80000000) + zero] + [zero] * 10 + [u32(640) + zero]
        h1 = _compress([mid[i] + zero for i in range(8)], w, wrap, jnp.invert, u32)
        w2 = h1 + [u32(0x80000000) + zero] + [zero] * 6 + [u32(256) + zero]
        h2 = _compress([u32(v) + zero for v in IV], w2, wrap, jnp.invert, u32)
        # digest word i, byte-swapped, is word 7 - i of the number (most
        # significant first); compare from the least significant up
        at_most = zero == zero
        for i in range(8):
            word, t = bswap(h2[i]), target[7 - i]
            at_most = (word < t) | ((word == t) & at_most)
        return jnp.min(jnp.where(at_most, offs, u32(tile)))

    return sweep


def target_words(target: int):
    """The target as eight 32-bit words, most significant first."""
    return [(target >> (32 * (7 - i))) & MASK for i in range(8)]


def first_share(sweep, tile: int, target: int, segments, depth: int = 4) -> Optional[int]:
    """The first global index of ``segments`` whose header meets
    ``target``, or None. Each hit is re-hashed with ``hashlib``."""
    import jax.numpy as jnp

    words = jnp.asarray(target_words(target), jnp.uint32)
    for header_hex, base, n_lo, n_hi in segments:
        header76 = bytes.fromhex(header_hex)
        mid = jnp.asarray(compress_host(IV, header76[:64]), jnp.uint32)
        tail = jnp.asarray([int.from_bytes(header76[64 + 4 * i:68 + 4 * i], "big")
                            for i in range(3)], jnp.uint32)
        pos, pending = n_lo, deque()
        while pos <= n_hi or pending:
            while len(pending) < depth and pos <= n_hi:
                take = min(tile, n_hi - pos + 1)
                pending.append((pos, take, sweep(mid, tail, words, jnp.uint32(pos))))
                pos += take
            start, take, off = pending.popleft()
            off = int(off)
            if off >= take:
                continue
            nonce = start + off
            if hash_value(header76 + nonce.to_bytes(4, "little")) > target:
                raise RuntimeError(f"device sweep and hashlib disagree at nonce {nonce}")
            return base + nonce
    return None


def _template(job: dict) -> str:
    """Every field of a job but its range: jobs that share it hash the
    same header at each index."""
    return json.dumps({k: v for k, v in job.items() if k not in ("lo", "hi")},
                      sort_keys=True)


def first_shares(sweep, tile: int, jobs: List[dict]) -> List[Optional[int]]:
    """The first share of each job, or None. Jobs on one template share
    their sweeps: a sweep from the lowest start still open stops at the
    first share ``f`` at or after it, and so settles every job that
    starts at or before ``f`` (its answer is ``f``, or none if it ends
    before ``f``); the jobs that start after ``f`` take the next sweep."""
    out: List[Optional[int]] = [None] * len(jobs)
    groups: dict = {}
    for i, job in enumerate(jobs):
        groups.setdefault(_template(job), []).append(i)
    for members in groups.values():
        todo = sorted(members, key=lambda i: jobs[i]["lo"])
        while todo:
            span = dict(jobs[todo[0]], hi=max(jobs[i]["hi"] for i in todo))
            first = first_share(sweep, tile, target_of(span), _segments(span))
            stop = span["hi"] if first is None else first
            print(f"reference: swept [{span['lo']}, {stop}], {stop - span['lo'] + 1} "
                  f"indices: first share {first}", file=sys.stderr, flush=True)
            rest = []
            for i in todo:
                if jobs[i]["lo"] > stop:
                    rest.append(i)
                elif first is not None and first <= jobs[i]["hi"]:
                    out[i] = first
            todo = rest
    return out


def _main(src: str, dst: str) -> None:
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # XLA:CPU's fusion pass takes exponential time on unrolled rounds
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_disable_hlo_passes=fusion")
    import jax

    tile = 1 << 22 if jax.default_backend() == "tpu" else 1 << 12
    sweep = make_sweep(tile)
    with open(src) as fh:
        jobs = json.load(fh)
    out = first_shares(sweep, tile, jobs)
    with open(dst, "w") as fh:
        json.dump(out, fh)


if __name__ == "__main__":
    _main(sys.argv[1], sys.argv[2])
