"""On-device extranonce → merkle-root → header-midstate roll.

The BASELINE.json:9-10 capability: when a worker exhausts the 32-bit
header nonce space it bumps the coinbase extranonce, which changes the
coinbase txid, which changes the merkle root, which changes the header —
and therefore the SHA midstate the hot search kernels specialize on.
The reference has no analogue (its toy PoW has no headers); stratum
miners do this on the host. Here the whole chain

    extranonce → coinbase txid → branch fold → merkle root
               → header midstate + variable tail words

runs as ONE jitted device program (:func:`make_extranonce_roll_batch`), so a
>2^32 search never ships header bytes from the host: the roll's
``(midstate, tail_words)`` outputs stay on device and feed either the
jnp dynamic-header hash (``ops.sha256.header_digest_dyn``) or the
dynamic Pallas candidate kernel
(``kernels.pallas_search_candidates_hdr``) directly.

The roll is **batch-shaped**: :func:`make_extranonce_roll_batch` rolls
``B`` extranonces in ONE device call — ``(B,) u32 pairs → (B, 8)
midstates + (B, 3) tail batches`` — which is what lets a batched sweep
(``tpuminter.rolled``) cover many extranonce segments per dispatch
instead of re-entering host orchestration at every segment boundary.
:func:`roll_batch_deduped` layers the shared-compression discipline on
top (ISSUE 16): rows of a window that carry the same extranonce share
ONE roll evaluation, forked per row by a device gather.

Cost: ``3 + 3·len(branch)`` SHA-256 compressions per extranonce — per
2^32 nonces of search, i.e. ~1e-9 of the hot-loop work. The shared
sub-computations inside one roll are already single-evaluation: the
coinbase prefix blocks before the extranonce hole are compressed once
host-side into the template midstate, and the branch fold runs each
level as one batched :func:`_dsha256_pair` across all B rows.

Host reference semantics: ``chain.rolled_header`` /
``chain.CoinbaseTemplate`` (tests pin the device roll bit-equal).
"""

from __future__ import annotations

import struct
from functools import lru_cache
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpuminter.chain import HEADER_SIZE, SHA256_H0
from tpuminter.ops import sha256 as ops

__all__ = [
    "make_extranonce_roll_batch",
    "roll_batch_deduped",
]

_H0 = np.array(SHA256_H0, dtype=np.uint32)
#: FIPS padding block for a 64-byte message (the merkle pair hash)
_PAD512 = np.array([0x80000000] + [0] * 14 + [512], dtype=np.uint32)
#: second-hash block words 8..15 for a 32-byte digest message
_PAD256 = np.array([0x80000000, 0, 0, 0, 0, 0, 0, 256], dtype=np.uint32)


def _bcast(const: np.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a (k,) constant over ``like``'s leading batch dims."""
    return jnp.broadcast_to(
        jnp.asarray(const), like.shape[:-1] + const.shape
    )


def _dsha256_pair(left8: jnp.ndarray, right8: jnp.ndarray) -> jnp.ndarray:
    """Double SHA-256 of the 64-byte concatenation of two 32-byte hashes
    given as (..., 8) u32 big-endian word batches — one merkle tree edge,
    elementwise over leading batch dims."""
    h0 = _bcast(_H0, left8)
    state = ops.compress(h0, jnp.concatenate([left8, right8], axis=-1))
    state = ops.compress(state, _bcast(_PAD512, left8))
    return ops.compress(h0, jnp.concatenate([state, _bcast(_PAD256, left8)], axis=-1))


def _build_roll(
    header80: bytes,
    coinbase_prefix: bytes,
    coinbase_suffix: bytes,
    extranonce_size: int,
    branch: Sequence[bytes],
) -> Callable[[jnp.ndarray, jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]:
    """The traceable batch roll body (un-jitted): ``(B,) u32 × 2 →
    ((B, 8), (B, 3))``. Shared by both public factories; callers that
    fuse the roll into a larger program trace this directly."""
    if len(header80) != HEADER_SIZE:
        raise ValueError(f"header must be {HEADER_SIZE} bytes, got {len(header80)}")
    if not 1 <= extranonce_size <= 8:
        raise ValueError("extranonce_size must be in [1, 8]")
    for sib in branch:
        if len(sib) != 32:
            raise ValueError("merkle branch entries must be 32 bytes")

    # coinbase txid as a NonceTemplate: the extranonce is the "nonce
    # hole" (little-endian bytes at the prefix/suffix seam), so all the
    # midstate/partial-eval machinery applies to the coinbase hash too
    cb_message = coinbase_prefix + b"\x00" * extranonce_size + coinbase_suffix
    cb_template = ops._build_template(
        cb_message,
        len(coinbase_prefix),
        [(j, 8 * j) for j in range(extranonce_size)],
        double=True,
    )
    branch_words = [
        np.frombuffer(sib, dtype=">u4").astype(np.uint32) for sib in branch
    ]
    # header constants: words 0..8 of block 1 (version ‖ prev_hash) and
    # the time/bits tail words — big-endian u32 reads of the serialized
    # bytes, merkle-root bytes excluded
    hdr_head9 = np.frombuffer(header80[:36], dtype=">u4").astype(np.uint32)
    w_time, w_bits = struct.unpack(">2I", header80[68:76])
    time_bits = np.array([w_time, w_bits], dtype=np.uint32)

    def roll(en_hi: jnp.ndarray, en_lo: jnp.ndarray):
        txid = ops.sha256_batch(
            cb_template, en_hi.astype(jnp.uint32), en_lo.astype(jnp.uint32)
        )  # (B, 8) coinbase txid words (big-endian u32 of txid bytes)
        node = txid
        for sib in branch_words:
            # coinbase is leaf 0: the running node is always the LEFT
            # input at every level (index path all zeros)
            node = _dsha256_pair(node, _bcast(sib, node))
        # merkle root bytes land in the header verbatim (internal byte
        # order == digest byte order), so root words ARE header words:
        # block 1 = version ‖ prev_hash ‖ root[0:28]
        midstate = ops.compress(
            _bcast(_H0, node),
            jnp.concatenate([_bcast(hdr_head9, node), node[..., :7]], axis=-1),
        )
        tail_words = jnp.concatenate(
            [node[..., 7:8], _bcast(time_bits, node)], axis=-1
        )
        return midstate, tail_words

    return roll


def make_extranonce_roll_batch(
    header80: bytes,
    coinbase_prefix: bytes,
    coinbase_suffix: bytes,
    extranonce_size: int,
    branch: Sequence[bytes],
    *,
    jit: bool = True,
) -> Callable[[jnp.ndarray, jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]]:
    """Compile the device roll for one job: ONE device call rolls a
    whole extranonce batch — ``roll(en_hi (B,), en_lo (B,)) ->
    (midstates (B, 8) u32, tail_words (B, 3) u32)``. Row ``i`` is the
    SHA-256 state after the rolled header's first 64 bytes and the
    header tail words ``(merkle word 7, time, bits)`` for extranonce
    ``(en_hi[i] << 32) | en_lo[i]`` — exactly what
    ``ops.header_digest_dyn`` and the dynamic Pallas kernels consume.
    ``header80``'s merkle-root field is ignored (it is what the roll
    recomputes); version/prev/time/bits are baked as constants. ≡
    ``ops.header_template(chain.rolled_header(...).pack())``'s
    ``midstate``/``tail_words()`` for every extranonce (pinned
    bit-equal by tests/test_extranonce.py). This is the producer side
    of the batched rolled sweep (``tpuminter.rolled``): B segment
    midstates per dispatch instead of one host-orchestrated roll per
    segment.

    ``jit=False`` returns the traceable body for callers embedding the
    roll in their own jitted program.
    """
    if jit:
        return _cached_batch_roll(
            header80, coinbase_prefix, coinbase_suffix, extranonce_size,
            tuple(branch),
        )
    return _build_roll(
        header80, coinbase_prefix, coinbase_suffix, extranonce_size, branch
    )


@lru_cache(maxsize=32)
def _cached_batch_roll(header80, coinbase_prefix, coinbase_suffix,
                       extranonce_size, branch):
    return jax.jit(_build_roll(
        header80, coinbase_prefix, coinbase_suffix, extranonce_size, branch
    ))


def roll_batch_deduped(
    roll: Callable[[jnp.ndarray, jnp.ndarray], Tuple[jnp.ndarray, jnp.ndarray]],
    en_hi: np.ndarray,
    en_lo: np.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Roll each UNIQUE extranonce once and fork the result per row —
    the ISSUE 16 "compress the shared coinbase prefix once" discipline
    at whole-roll granularity: in a compute-bound window (nonce span ≥
    roll_batch × width) every row of the tile plan carries the SAME
    extranonce, and the plain batched roll re-computes the identical
    coinbase hash, branch fold, and midstate compress B times.

    Row ``i`` of the output is bit-for-bit the plain
    ``roll(en_hi, en_lo)`` row ``i``: the roll is elementwise over its
    batch dim, so rolling the unique set and gathering is the same u32
    arithmetic per lane (integer ops — no reassociation hazard).
    Uniques are padded to the next power of two so the jitted roll sees
    at most ``log2(B)+1`` distinct shapes instead of one per duplicate
    pattern (the shape-bucketing rule ``rolled.lean_plan`` established).

    Why not the fully-unrolled symbolic roll instead: measured 11x
    faster steady-state (0.675 → 0.061 ms/call) but ~40 s trace+compile
    PER JOB vs ~1 s — a job-change latency regression no steady-state
    win covers at ~30 compressions/window. Recorded as a PERF.md §Round
    14 rejection; this host-side dedup captures the duplicate-row share
    of that win with zero new compiled programs.
    """
    en = (en_hi.astype(np.uint64) << np.uint64(32)) | en_lo.astype(np.uint64)
    uniq, inv = np.unique(en, return_inverse=True)
    if len(uniq) == len(en):
        return roll(jnp.asarray(en_hi), jnp.asarray(en_lo))
    n = 1 << max(0, int(len(uniq) - 1).bit_length())
    padded = np.concatenate([uniq, np.repeat(uniq[:1], n - len(uniq))])
    mids, tails = roll(
        jnp.asarray((padded >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray((padded & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
    )
    idx = jnp.asarray(inv.astype(np.int32))
    return mids[idx], tails[idx]
