"""On-device extranonce → merkle-root → header-midstate roll.

The BASELINE.json:9-10 capability: when a worker exhausts the 32-bit
header nonce space it bumps the coinbase extranonce, which changes the
coinbase txid, which changes the merkle root, which changes the header —
and therefore the SHA midstate the hot search kernels specialize on.
The reference has no analogue (its toy PoW has no headers); stratum
miners do this on the host. Here the whole chain

    extranonce → coinbase txid → branch fold → merkle root
               → header midstate + variable tail words

runs as ONE jitted device program (:func:`extranonce_roll`), so a
>2^32 search never ships header bytes from the host: the roll's
``(midstate, tail_words)`` outputs stay on device and feed either the
jnp dynamic-header hash (``ops.sha256.header_digest_dyn``) or the
dynamic Pallas candidate kernel
(``kernels.pallas_search_candidates_hdr``) directly.

**The template is an operand.** A Stratum ``clean_jobs`` notify brings a
new coinbase, branch and header every job, so nothing of a template is
baked into the program: :func:`roll_template` splits it on the host into
a static layout (the coinbase's prefix and suffix lengths and the
extranonce size) and its words (the coinbase midstate before the hole,
the coinbase words from the hole on, the branch, the header's words).
:func:`extranonce_roll` is compiled once per layout and batch shape, and
every later template of that layout is new operands only.

The roll is **batch-shaped**: :func:`roll_batch_deduped` rolls a
window's ``B`` extranonces in ONE device call — ``(B,) u32 pairs →
(B, 8) midstates + (B, 3) tail batches`` — which is what lets a batched
sweep (``tpuminter.rolled``) cover many extranonce segments per dispatch
instead of re-entering host orchestration at every segment boundary.
Rows of a window that carry the same extranonce share ONE roll
evaluation, forked per row by a device gather inside the program.

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
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpuminter.chain import HEADER_SIZE, SHA256_H0
from tpuminter.ops import sha256 as ops

__all__ = [
    "extranonce_roll",
    "roll_batch_deduped",
    "roll_template",
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


def roll_template(
    header80: bytes,
    coinbase_prefix: bytes,
    coinbase_suffix: bytes,
    extranonce_size: int,
    branch: Sequence[bytes],
) -> Tuple[ops.HoleLayout, Tuple[np.ndarray, ...]]:
    """Split one job's template into the roll's static layout and its
    words, on the host: ``(layout, (cb_midstate (8,), cb_tail (n, 16),
    branch (L, 8), head9 (9,), time_bits (2,)))``.

    The coinbase is a message with an extranonce-shaped hole
    (``ops.plan_hole``, little-endian bytes at the prefix/suffix seam):
    its whole 64-byte blocks before the hole are compressed here into
    ``cb_midstate``, and ``cb_tail`` holds its words from the hole's
    block on. ``layout`` depends only on the prefix length, the suffix
    length and the extranonce size; the branch depth is the branch
    operand's shape. The header's words are version ‖ prev_hash
    (``head9``) and time, bits (``time_bits``): big-endian u32 reads of
    the serialized bytes, the merkle-root field excluded (the roll
    recomputes it).
    """
    if len(header80) != HEADER_SIZE:
        raise ValueError(f"header must be {HEADER_SIZE} bytes, got {len(header80)}")
    if not 1 <= extranonce_size <= 8:
        raise ValueError("extranonce_size must be in [1, 8]")
    for sib in branch:
        if len(sib) != 32:
            raise ValueError("merkle branch entries must be 32 bytes")
    layout, cb_mid, cb_tail = ops.plan_hole(
        coinbase_prefix + b"\x00" * extranonce_size + coinbase_suffix,
        len(coinbase_prefix),
        [(j, 8 * j) for j in range(extranonce_size)],
        double=True,
    )
    branch_words = (
        np.frombuffer(b"".join(branch), dtype=">u4")
        .astype(np.uint32)
        .reshape(len(branch), 8)
    )
    head9 = np.frombuffer(header80[:36], dtype=">u4").astype(np.uint32)
    time_bits = np.array(struct.unpack(">2I", header80[68:76]), dtype=np.uint32)
    return layout, (cb_mid, cb_tail, branch_words, head9, time_bits)


@partial(jax.jit, static_argnums=0)
def extranonce_roll(
    layout: ops.HoleLayout,
    cb_midstate: jnp.ndarray,
    cb_tail: jnp.ndarray,
    branch: jnp.ndarray,
    head9: jnp.ndarray,
    time_bits: jnp.ndarray,
    en_hi: jnp.ndarray,
    en_lo: jnp.ndarray,
    rows: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The roll, one compiled program per layout: ``(U,) u32 × 2, (R,)
    i32 → ((R, 8), (R, 3))`` for a template given as
    :func:`roll_template`'s words. Output row ``i`` is the roll of
    extranonce ``(en_hi[rows[i]] << 32) | en_lo[rows[i]]``: the SHA-256
    state after the rolled header's first 64 bytes and the header tail
    words ``(merkle word 7, time, bits)``, exactly what
    ``ops.header_digest_dyn`` and the dynamic Pallas kernels consume
    (≡ ``ops.header_template(chain.rolled_header(...).pack())``'s
    ``midstate``/``tail_words()``, pinned bit-equal by
    tests/test_extranonce.py and tests/test_roll_template.py). Only
    ``layout`` is static (and the shapes: the branch depth and the
    batch), so a fresh template of a layout already seen is a new set
    of operands, never a new trace or compile.

    The coinbase hash and the header's first block are scanned
    compressions (``unroll=False``), and the branch is one ``lax.scan``
    over its levels, so the program does not grow with the depth: the
    roll is ``3 + 3·depth`` compressions per extranonce against 2^27 and
    more nonces of sweep behind it, and all of them unrolled flat cost
    ~2 min of tracing and compiling per program for a TPU target.
    """
    txid = ops.sha256_batch_dyn(
        layout, cb_midstate, cb_tail,
        en_hi.astype(jnp.uint32), en_lo.astype(jnp.uint32), unroll=False,
    )  # (U, 8) coinbase txid words (big-endian u32 of txid bytes)

    def level(node, sib):
        # coinbase is leaf 0: the running node is always the LEFT input
        # at every level (index path all zeros)
        return _dsha256_pair(node, _bcast(sib, node)), None

    node, _ = jax.lax.scan(level, txid, branch)
    # merkle root bytes land in the header verbatim (internal byte
    # order == digest byte order), so root words ARE header words:
    # block 1 = version ‖ prev_hash ‖ root[0:28]
    midstate = ops.compress(
        _bcast(_H0, node),
        jnp.concatenate([_bcast(head9, node), node[..., :7]], axis=-1),
        unroll=False,
    )
    tail_words = jnp.concatenate(
        [node[..., 7:8], _bcast(time_bits, node)], axis=-1
    )
    return midstate[rows], tail_words[rows]


def roll_batch_deduped(
    layout: ops.HoleLayout,
    words: Sequence[jnp.ndarray],
    en_hi: np.ndarray,
    en_lo: np.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One window's roll of a template (:func:`roll_template`'s
    ``layout`` and ``words``, the words on the device or the host): row
    ``i`` of the output is extranonce ``(en_hi[i] << 32) | en_lo[i]``'s
    ``(midstate (8,), tail_words (3,))``, all rows from ONE device call.

    It rolls each UNIQUE extranonce once and forks the result per row —
    the ISSUE 16 "compress the shared coinbase prefix once" discipline
    at whole-roll granularity: in a compute-bound window (nonce span ≥
    roll_batch × width) every row of the tile plan carries the SAME
    extranonce, and the plain batched roll re-computes the identical
    coinbase hash, branch fold, and midstate compress B times.

    Row ``i`` of the output is bit-for-bit the roll of row ``i`` alone:
    the roll is elementwise over its batch dim, so rolling the unique
    set and gathering (inside the roll's own program, its ``rows``
    operand) is the same u32 arithmetic per lane (integer ops — no
    reassociation hazard). Uniques are padded to the next power of two
    so the roll sees at most ``log2(B)+1`` distinct shapes instead of
    one per duplicate pattern (the shape-bucketing rule
    ``rolled.lean_plan`` established).

    Why not the fully-unrolled symbolic roll instead: measured 11x
    faster steady-state (0.675 → 0.061 ms/call) on the CPU but ~40 s
    trace+compile per program, which no steady-state win covers at ~30
    compressions a window; this host-side dedup captures the
    duplicate-row share of that win with no new program.
    """
    en = (en_hi.astype(np.uint64) << np.uint64(32)) | en_lo.astype(np.uint64)
    uniq, inv = np.unique(en, return_inverse=True)
    n = 1 << max(0, int(len(uniq) - 1).bit_length())
    padded = np.concatenate([uniq, np.repeat(uniq[:1], n - len(uniq))])
    return extranonce_roll(
        layout, *words,
        jnp.asarray((padded >> np.uint64(32)).astype(np.uint32)),
        jnp.asarray((padded & np.uint64(0xFFFFFFFF)).astype(np.uint32)),
        jnp.asarray(inv.astype(np.int32).reshape(-1)),
    )
