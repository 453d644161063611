"""Pallas double-SHA-256: the BASELINE.json:5 hot-loop kernels.

All generated from a :class:`~tpuminter.ops.sha256.NonceTemplate`
via the partial-evaluating symbolic compress (``ops.symbolic``), so every
message constant — midstate, padding, constant schedule words, constant
early rounds, ``K+W`` folds — is baked into the instruction stream at
trace time and the VPU only ever touches nonce-dependent values:

- :func:`pallas_sha256_batch` — digests for an explicit nonce vector
  (the correctness surface; bit-identical to ``ops.sha256_batch``).
- :func:`pallas_search_candidates` — the PRODUCTION search: nonces are
  generated *in-register* from a scalar base (zero HBM input traffic)
  and early-rejected on the hash's top 64 bits only, two rounds short
  of a full second compression (``sym.compress_sym_e60_e61``); rare
  survivors are verified host-side (``tpuminter.search``). This is the
  ≥1 GH/s/chip path.
- :func:`pallas_search_target` — full in-kernel 256-bit target compare
  plus the running lexicographic-min fold (exact exhausted-range
  minimum); slower, used when exact-min semantics are required.

Layout: work arrays are ``(32, 128)`` u32 tiles (see ``_TILE``) with a
``lax.while_loop`` striding tiles and ``tiles_per_step`` independent
dependency chains in flight. Rotations lower to shift/or pairs
(pallas_guide: TPUs have no rotate ISA).

The kernels set ``interpret=True`` on the CPU backend, but the unrolled
~6k-op bodies make interpreter-mode execution impractically slow beyond
tiny shapes (measured round 4: one minimum-size 1024-nonce
``pallas_sha256_batch`` did not finish in 400 s on this host); CPU CI
pins the *generator* (``ops.symbolic``) against the jnp path instead,
and tests/test_kernels_tpu.py exercises the compiled kernels on a real
chip (see that module's rationale).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpuminter.ops import sha256 as ops
from tpuminter.ops import symbolic as sym

__all__ = [
    "pallas_sha256_batch",
    "pallas_search_target",
    "pallas_search_candidates",
    "pallas_search_candidates_hdr",
    "pallas_search_candidates_hdr_batch",
]

LANES = 128


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _as_rows(n: int, block_rows: int) -> Tuple[int, int]:
    if n % (block_rows * LANES) != 0:
        raise ValueError(
            f"batch {n} must be a multiple of block_rows*128 = {block_rows * LANES}"
        )
    rows = n // LANES
    return rows, rows // block_rows


# ---------------------------------------------------------------------------
# digests kernel (correctness surface)
# ---------------------------------------------------------------------------

def _digest_kernel(template, hi_ref, lo_ref, out_ref):
    digest = sym.double_sha256_sym(template, hi_ref[...], lo_ref[...])
    for i in range(8):
        word = digest[i]
        if isinstance(word, int):  # nonce never reached this word
            word = jnp.full(hi_ref.shape, word, jnp.uint32)
        out_ref[i] = word


@partial(jax.jit, static_argnums=(0, 3))
def pallas_sha256_batch(
    template: ops.NonceTemplate,
    nonce_hi: jnp.ndarray,
    nonce_lo: jnp.ndarray,
    block_rows: int = 8,
) -> jnp.ndarray:
    """Digest words for a nonce batch: ``(N,) u32 × 2 → (N, 8) u32``.
    Drop-in equivalent of ``ops.sha256_batch`` (tests pin them equal)."""
    n = nonce_lo.shape[0]
    rows, grid = _as_rows(n, block_rows)
    out = pl.pallas_call(
        partial(_digest_kernel, template),
        out_shape=jax.ShapeDtypeStruct((8, rows, LANES), jnp.uint32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(
                (block_rows, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
            )
        ]
        * 2,
        out_specs=pl.BlockSpec(
            (8, block_rows, LANES), lambda i: (0, i, 0), memory_space=pltpu.VMEM
        ),
        interpret=_interpret(),
    )(nonce_hi.reshape(rows, LANES), nonce_lo.reshape(rows, LANES))
    return out.transpose(1, 2, 0).reshape(n, 8)


# ---------------------------------------------------------------------------
# fused search kernel (performance surface)
# ---------------------------------------------------------------------------

#: summary row layout (one 128-lane row per call)
_FOUND, _FIRST_IDX, _MIN_HW0, _MIN_IDX = 0, 1, 2, 10

_U32MAX = np.uint32(0xFFFFFFFF)
_I32MAX = np.int32(0x7FFFFFFF)
#: work-array shape per "tile": 32 sublane rows × 128 lanes = 4096 nonces.
#: Taller-than-vreg tiles (4 native (8,128) vregs per op) measurably beat
#: 8-row tiles on v5e (~+8% GH/s): each traced op covers 4× the work, so
#: the unrolled SHA body has 4× fewer instructions to fetch/schedule,
#: while `tiles_per_step` still provides independent dependency chains.
_TILE = (32, LANES)


def _bias_const(t: int) -> np.int32:
    """u32 constant → the sign-biased int32 domain (order-preserving)."""
    b = int(t) ^ 0x80000000
    return np.int32(b - (1 << 32) if b >= (1 << 31) else b)


def _hash_words_biased(digest):
    """Digest words → hash-value words (msb-first), sign-biased int32.

    Mosaic has no unsigned reductions/compares; u32 order == i32 order
    after XOR 0x80000000, so all folding happens in the biased domain.
    """
    out = []
    for j in range(8):
        word = sym.xor(
            sym.shl(sym.and_(digest[7 - j], 0x000000FF), 24),
            sym.shl(sym.and_(digest[7 - j], 0x0000FF00), 8),
            sym.shr(sym.and_(digest[7 - j], 0x00FF0000), 8),
            sym.shr(sym.and_(digest[7 - j], 0xFF000000), 24),
        )
        out.append(
            jax.lax.bitcast_convert_type(sym.xor(word, 0x80000000), jnp.int32)
        )
    return out


def _search_kernel(template, target_words, n_tiles, tiles_per_step,
                   track_min, n_valid, base_ref, out_ref):
    """Whole-chunk search in ONE kernel invocation.

    A ``lax.while_loop`` sweeps ``n_tiles`` ``_TILE``-shaped tiles — 4096
    nonces each, ``tiles_per_step`` of them interleaved per iteration so the
    VPU has independent SHA dependency chains in flight (ILP) — with
    EARLY EXIT as soon as any step hits the target. A single call covers
    an arbitrarily large range with zero host syncs mid-sweep while the live register set stays a few tiles
    wide. All folds are elementwise per lane across tiles; the
    cross-lane reduction happens once, after the loop.
    """
    tgt = [_bias_const(t) for t in target_words]
    offs = (
        jax.lax.broadcasted_iota(jnp.int32, _TILE, 0) * np.int32(LANES)
        + jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    )
    base = base_ref[0]
    limit = np.int32(n_valid)
    tile_sz = _TILE[0] * LANES

    def cond(carry):
        i, found, _, _ = carry
        return (i < n_tiles) & (found == 0)

    def body(carry):
        i, _, first_offs, (min_words, min_offs) = carry
        any_ok = jnp.zeros(_TILE, jnp.bool_)
        for t in range(tiles_per_step):
            offs_i = offs + (i + t) * np.int32(tile_sz)
            nonces = base + jax.lax.bitcast_convert_type(offs_i, jnp.uint32)
            # hi nonce half is constant 0 → its bytes fold out
            digest = sym.double_sha256_sym(template, 0, nonces)
            hwb = _hash_words_biased(digest)
            # target compare, lexicographic over baked constants
            lt = jnp.zeros(_TILE, jnp.bool_)
            eq = jnp.ones(_TILE, jnp.bool_)
            for j in range(8):
                lt = lt | (eq & (hwb[j] < tgt[j]))
                eq = eq & (hwb[j] == tgt[j])
            ok = (lt | eq) & (offs_i < limit)  # pad lanes can't win
            any_ok = any_ok | ok
            first_offs = jnp.where(
                ok & (offs_i < first_offs), offs_i, first_offs
            )
            if track_min:
                # elementwise lexicographic min fold vs carried best
                c_lt = jnp.zeros(_TILE, jnp.bool_)
                c_eq = jnp.ones(_TILE, jnp.bool_)
                for j in range(8):
                    c_lt = c_lt | (c_eq & (hwb[j] < min_words[j]))
                    c_eq = c_eq & (hwb[j] == min_words[j])
                c_lt = c_lt & (offs_i < limit)
                min_words = tuple(
                    jnp.where(c_lt, hwb[j], min_words[j]) for j in range(8)
                )
                min_offs = jnp.where(c_lt, offs_i, min_offs)
        # one cross-lane reduction per step, not per tile
        found = jnp.max(any_ok.astype(jnp.int32))
        return (
            i + tiles_per_step, found, first_offs, (min_words, min_offs)
        )

    init = (
        jnp.int32(0),
        jnp.int32(0),
        jnp.full(_TILE, _I32MAX, jnp.int32),
        (tuple(jnp.full(_TILE, _I32MAX, jnp.int32) for _ in range(8)),
         jnp.full(_TILE, _I32MAX, jnp.int32)),
    )
    _, found, first_offs, (min_words, min_offs) = jax.lax.while_loop(
        cond, body, init
    )
    first = jnp.min(first_offs)
    # cross-lane lexicographic argmin: 8 min+mask passes, then min-offset
    # tie-break (= lowest nonce; earlier tiles already won elementwise)
    mask = jnp.ones(_TILE, jnp.bool_)
    final_words = []
    for j in range(8):
        col = jnp.where(mask, min_words[j], _I32MAX)
        m = jnp.min(col)
        mask = mask & (col == m)
        final_words.append(m)
    min_idx = jnp.min(jnp.where(mask, min_offs, _I32MAX))
    # summary row via lane-index select (no scalar scatters); words are
    # un-biased back to u32 on the way out
    lane = jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    row = jnp.zeros(_TILE, jnp.int32)
    for idx, val in (
        [(_FOUND, found), (_FIRST_IDX, first), (_MIN_IDX, min_idx)]
        + [(_MIN_HW0 + j, final_words[j] ^ np.int32(-0x80000000))
           for j in range(8)]
    ):
        row = jnp.where(lane == np.int32(idx), val, row)
    out_ref[...] = jax.lax.bitcast_convert_type(row, jnp.uint32)


@partial(jax.jit, static_argnums=(0, 1, 3, 4, 5))
def pallas_search_target(
    template: ops.NonceTemplate,
    target_words: Tuple[int, ...],
    base: jnp.ndarray,
    n: int,
    tiles_per_step: int = 8,
    track_min: bool = True,
):
    """Fused search over up to ``n`` consecutive nonces from scalar
    ``base`` (``n`` is rounded UP internally to a whole number of loop
    steps; lanes past the true ``n`` are masked out of every fold, so any
    ``n >= 1`` is valid).

    Returns ``(found, first_nonce_off, min_hash_words (8,), min_off)``;
    offsets are relative to ``base``. ``target_words`` are msb-first u32
    ints (``ops.target_to_words``), static so the compare folds into the
    kernel. One device call, one host sync, in-kernel early exit: when a
    hit occurs the loop stops within ``tiles_per_step × 4096`` nonces.
    ``first_nonce_off`` is exact (the lowest winning offset).
    """
    if not 1 <= n <= 1 << 30:
        raise ValueError("n must be in [1, 2^30] (int32 offset domain)")
    chunk = _TILE[0] * LANES * tiles_per_step
    n_tiles = -(-n // chunk) * tiles_per_step  # round up to whole steps
    summary = pl.pallas_call(
        partial(_search_kernel, template,
                tuple(int(t) for t in target_words), n_tiles,
                tiles_per_step, track_min, n),
        out_shape=jax.ShapeDtypeStruct(_TILE, jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(base.reshape(1).astype(jnp.uint32))
    row = summary[0]
    found = row[_FOUND]
    first_off = row[_FIRST_IDX]
    min_words = row[_MIN_HW0 : _MIN_HW0 + 8]
    min_off = row[_MIN_IDX]
    return found, first_off, min_words, min_off


# ---------------------------------------------------------------------------
# candidate kernel: the production TARGET hot path
# ---------------------------------------------------------------------------

def _cand_kernel(template, n_tiles, tiles_per_step, n_valid, mask_tail,
                 base_ref, cap_ref, out_ref):
    """Early-reject sweep: find the first offset whose double-SHA hash
    value's top 64 bits clear the bar — word 0 (byteswapped digest word
    7) must be ZERO (necessary for every real target) and word 1
    (byteswapped digest word 6) must be ≤ a *dynamic* cap carried in
    SMEM (the target's second word — dynamic so one compiled kernel
    serves every difficulty). Per nonce this computes only ``(e60,
    e61)`` of the second compression (``sym.double_sha256_e60_e61``),
    one equality against the baked :data:`sym.CAND_E60`, and one
    biased compare; no final adds, no 256-bit compare, no min fold —
    full evaluation happens host-side for the rare survivors. With the
    cap at the target's real word 1 the false-survivor rate is ~2^-64,
    so sweeps essentially never early-exit without a true win.
    Tail-lane masking is emitted only when ``n`` is not a whole number
    of steps (``mask_tail``), keeping the hot loop free of it for
    power-of-two slabs."""
    cand_c = np.uint32(sym.CAND_E60)
    offs = (
        jax.lax.broadcasted_iota(jnp.int32, _TILE, 0) * np.int32(LANES)
        + jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    )
    base = base_ref[0]
    # hash word 1 cap: pre-biased into the signed-compare domain on the
    # host (Mosaic has no scalar bitcast)
    cap1 = cap_ref[0]
    limit = np.int32(n_valid)
    tile_sz = _TILE[0] * LANES

    def cond(carry):
        i, found, _ = carry
        return (i < n_tiles) & (found == 0)

    def body(carry):
        i, _, first_offs = carry
        any_ok = jnp.zeros(_TILE, jnp.bool_)
        for t in range(tiles_per_step):
            offs_i = offs + (i + t) * np.int32(tile_sz)
            nonces = base + jax.lax.bitcast_convert_type(offs_i, jnp.uint32)
            e60, e61 = sym.double_sha256_e60_e61(template, 0, nonces)
            digest6 = sym.add(sym.DIGEST6_BIAS, e61)
            hw1 = sym.xor(
                sym.shl(sym.and_(digest6, 0x000000FF), 24),
                sym.shl(sym.and_(digest6, 0x0000FF00), 8),
                sym.shr(sym.and_(digest6, 0x00FF0000), 8),
                sym.shr(sym.and_(digest6, 0xFF000000), 24),
                0x80000000,
            )
            hw1b = jax.lax.bitcast_convert_type(hw1, jnp.int32)
            ok = (e60 == cand_c) & (hw1b <= cap1)
            if mask_tail:
                ok = ok & (offs_i < limit)
            any_ok = any_ok | ok
            first_offs = jnp.where(
                ok & (offs_i < first_offs), offs_i, first_offs
            )
        found = jnp.max(any_ok.astype(jnp.int32))
        return (i + tiles_per_step, found, first_offs)

    init = (jnp.int32(0), jnp.int32(0), jnp.full(_TILE, _I32MAX, jnp.int32))
    _, found, first_offs = jax.lax.while_loop(cond, body, init)
    first = jnp.min(first_offs)
    lane = jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    row = jnp.where(lane == np.int32(_FOUND), found, jnp.zeros(_TILE, jnp.int32))
    row = jnp.where(lane == np.int32(_FIRST_IDX), first, row)
    out_ref[...] = jax.lax.bitcast_convert_type(row, jnp.uint32)


@partial(jax.jit, static_argnums=(0, 2, 3))
def pallas_search_candidates(
    template: ops.NonceTemplate,
    base: jnp.ndarray,
    n: int,
    tiles_per_step: int = 8,
    hw1_cap: jnp.ndarray | None = None,
    stop: jnp.ndarray | None = None,
):
    """Fast sweep of ``n`` consecutive nonces from scalar ``base`` for
    *candidates*: nonces whose double-SHA-256 hash value has top word
    zero AND second word ≤ ``hw1_cap`` (a dynamic u32 scalar — pass the
    target's word 1 so a candidate is a true win up to a ~2^-64
    tail; defaults to 0xFFFFFFFF, i.e. the pure top-word-zero test).
    Top word zero is a necessary condition for ``hash <= target`` at
    every real difficulty (the Bitcoin target's top word is 0 from
    difficulty 1 up), so the sweep can never miss a winner.

    Returns ``(found, first_off)``: ``found != 0`` iff a candidate lies
    in range, ``first_off`` its lowest offset from ``base``. The kernel
    early-exits within ``tiles_per_step × 4096`` nonces of a candidate;
    offsets past the first candidate are NOT searched (the caller owns
    host-side verification + remainder re-issue —
    ``tpuminter.search.CandidateSearch``). The hot loop carries no
    byteswap/256-bit-compare/min-fold baggage — full evaluation happens
    host-side for the rare survivors.

    ``stop`` chains the sweep behind the one dispatched just before it:
    that sweep's packed handle (``search.pack_handle``: found,
    first_off, skipped). When the handle reports found or skipped, this
    sweep does no nonce work and returns ``(0, 0, 1)``; otherwise
    ``(found, first_off, 0)``. The test is a ``lax.cond`` around the
    kernel on the device, so a slab queued behind a candidate costs no
    host round trip and the kernel itself is the same. Without ``stop``
    the program is the unchained one, returning ``(found, first_off)``."""
    if not 1 <= n <= 1 << 30:
        raise ValueError("n must be in [1, 2^30] (int32 offset domain)")
    if hw1_cap is None:
        hw1_cap = jnp.uint32(0xFFFFFFFF)
    if stop is not None:
        # the working branch is the unchained program, nested: its
        # kernel op keeps the name a device trace shows for it
        zero = jnp.uint32(0)
        return jax.lax.cond(
            (stop[0] != 0) | (stop[2] != 0),
            lambda: (zero, zero, jnp.uint32(1)),
            lambda: (*pallas_search_candidates(
                template, base, n, tiles_per_step, hw1_cap), zero),
        )
    chunk = _TILE[0] * LANES * tiles_per_step
    n_tiles = -(-n // chunk) * tiles_per_step
    cap_biased = jax.lax.bitcast_convert_type(
        hw1_cap.astype(jnp.uint32) ^ jnp.uint32(0x80000000), jnp.int32
    )
    summary = pl.pallas_call(
        partial(_cand_kernel, template, n_tiles, tiles_per_step, n,
                n % chunk != 0),
        out_shape=jax.ShapeDtypeStruct(_TILE, jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 2,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(base.reshape(1).astype(jnp.uint32), cap_biased.reshape(1))
    row = summary[0]
    return row[_FOUND], row[_FIRST_IDX]


# ---------------------------------------------------------------------------
# dynamic-header candidate kernel (the extranonce-roll consumer)
# ---------------------------------------------------------------------------

def _cand_hdr_kernel(n_tiles, tiles_per_step, n_valid, mask_tail,
                     mid_ref, tw_ref, base_ref, cap_ref, out_ref):
    """Early-reject sweep over a header whose midstate and variable tail
    words arrive in SMEM at *runtime* instead of being baked at trace
    time: the consumer of the on-device extranonce roll
    (``ops.merkle.make_extranonce_roll_batch`` → this kernel, zero host
    round-trips per roll, BASELINE.json:9-10) — and, as a bonus, a
    single compiled kernel that serves EVERY header-mining job (no
    ~20-40 s per-job compile).

    Identical candidate test to ``_cand_kernel``; the only cost of
    dynamism is the partial-eval folds the symbolic compress can no
    longer do (the first tail compression's early rounds and its
    constant-word ``K+W`` folds), a few percent of the instruction
    stream."""
    mid = [mid_ref[i] for i in range(8)]
    tail = [tw_ref[0], tw_ref[1], tw_ref[2], 0] + list(ops.HEADER_TAIL_PAD)
    cand_c = np.uint32(sym.CAND_E60)
    offs = (
        jax.lax.broadcasted_iota(jnp.int32, _TILE, 0) * np.int32(LANES)
        + jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    )
    base = base_ref[0]
    cap1 = cap_ref[0]
    limit = np.int32(n_valid)
    tile_sz = _TILE[0] * LANES

    def cond(carry):
        i, found, _ = carry
        return (i < n_tiles) & (found == 0)

    def body(carry):
        i, _, first_offs = carry
        any_ok = jnp.zeros(_TILE, jnp.bool_)
        for t in range(tiles_per_step):
            offs_i = offs + (i + t) * np.int32(tile_sz)
            nonces = base + jax.lax.bitcast_convert_type(offs_i, jnp.uint32)
            e60, e61 = sym.hash_sym_e60_e61(
                mid, [tail], ops.HEADER_NONCE_POSITIONS, 0, nonces
            )
            digest6 = sym.add(sym.DIGEST6_BIAS, e61)
            hw1 = sym.xor(
                sym.shl(sym.and_(digest6, 0x000000FF), 24),
                sym.shl(sym.and_(digest6, 0x0000FF00), 8),
                sym.shr(sym.and_(digest6, 0x00FF0000), 8),
                sym.shr(sym.and_(digest6, 0xFF000000), 24),
                0x80000000,
            )
            hw1b = jax.lax.bitcast_convert_type(hw1, jnp.int32)
            ok = (e60 == cand_c) & (hw1b <= cap1)
            if mask_tail:
                ok = ok & (offs_i < limit)
            any_ok = any_ok | ok
            first_offs = jnp.where(
                ok & (offs_i < first_offs), offs_i, first_offs
            )
        found = jnp.max(any_ok.astype(jnp.int32))
        return (i + tiles_per_step, found, first_offs)

    init = (jnp.int32(0), jnp.int32(0), jnp.full(_TILE, _I32MAX, jnp.int32))
    _, found, first_offs = jax.lax.while_loop(cond, body, init)
    first = jnp.min(first_offs)
    lane = jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    row = jnp.where(lane == np.int32(_FOUND), found, jnp.zeros(_TILE, jnp.int32))
    row = jnp.where(lane == np.int32(_FIRST_IDX), first, row)
    out_ref[...] = jax.lax.bitcast_convert_type(row, jnp.uint32)


@partial(jax.jit, static_argnums=(3, 4))
def pallas_search_candidates_hdr(
    midstate8: jnp.ndarray,
    tailw3: jnp.ndarray,
    base: jnp.ndarray,
    n: int,
    tiles_per_step: int = 8,
    hw1_cap: jnp.ndarray | None = None,
):
    """Dynamic-header twin of :func:`pallas_search_candidates`: the
    header midstate (8 u32) and variable tail words (merkle word 7,
    time, bits) are runtime device values — pass one row of
    ``ops.merkle.make_extranonce_roll_batch``'s outputs straight in;
    they never visit the host. Same return contract: ``(found, first_off)``."""
    if not 1 <= n <= 1 << 30:
        raise ValueError("n must be in [1, 2^30] (int32 offset domain)")
    if hw1_cap is None:
        hw1_cap = jnp.uint32(0xFFFFFFFF)
    chunk = _TILE[0] * LANES * tiles_per_step
    n_tiles = -(-n // chunk) * tiles_per_step
    cap_biased = jax.lax.bitcast_convert_type(
        hw1_cap.astype(jnp.uint32) ^ jnp.uint32(0x80000000), jnp.int32
    )
    summary = pl.pallas_call(
        partial(_cand_hdr_kernel, n_tiles, tiles_per_step, n,
                n % chunk != 0),
        out_shape=jax.ShapeDtypeStruct(_TILE, jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 4,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(
        midstate8.astype(jnp.uint32),
        tailw3.astype(jnp.uint32),
        base.reshape(1).astype(jnp.uint32),
        cap_biased.reshape(1),
    )
    row = summary[0]
    return row[_FOUND], row[_FIRST_IDX]


def _cand_hdr_batch_kernel(n_tiles, tiles_per_step,
                           mid_ref, tw_ref, base_ref, lim_ref, cap_ref,
                           out_ref):
    """One grid step = one roll ROW of the batched sweep: identical
    candidate test to ``_cand_hdr_kernel``, but the row's midstate, tail
    words, nonce base AND valid count all arrive per-row at runtime
    (row ``pl.program_id(0)`` of the whole ``make_extranonce_roll_batch``
    output, which sits in SMEM unblocked: Mosaic refuses ``(1, 8)``
    SMEM blocks of an ``(B, 8)`` array). The valid count is dynamic because rows are the ragged
    ``chain.rolled_tiles`` of an arbitrary global window — the loop
    bound trims to it (a ``valid == 0`` padding row costs zero sweep
    iterations) and the candidate mask applies it exactly.

    The row's shared message-schedule prefix (ISSUE 16) — rounds 0-2
    plus the nonce-free parts of w16-w19 — is hoisted out of the tile
    loop via ``sym.prepare_hdr``: everything that depends only
    on (midstate, merkle word 7, time, bits) is computed once per grid
    step as 0-d scalars instead of once per tile. Mosaic does not LICM
    scalar work out of ``while_loop`` bodies on its own, so the hoist
    must be structural. Same booleans bit for bit (the prepared finisher
    is pinned against ``hash_sym_e60_e61`` in tier-1)."""
    r = pl.program_id(0)
    mid = [mid_ref[r, i] for i in range(8)]
    tail = [tw_ref[r, 0], tw_ref[r, 1], tw_ref[r, 2], 0] + list(
        ops.HEADER_TAIL_PAD
    )
    cand_c = np.uint32(sym.CAND_E60)
    offs = (
        jax.lax.broadcasted_iota(jnp.int32, _TILE, 0) * np.int32(LANES)
        + jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    )
    base = base_ref[r]
    cap1 = cap_ref[0]
    limit = lim_ref[r]  # dynamic i32 valid count, NOT a trace constant
    tile_sz = _TILE[0] * LANES
    prep = sym.prepare_hdr(mid, tail[0], tail[1], tail[2])

    def cond(carry):
        i, found, _ = carry
        return (i < n_tiles) & (found == 0) & (i * np.int32(tile_sz) < limit)

    def body(carry):
        i, _, first_offs = carry
        any_ok = jnp.zeros(_TILE, jnp.bool_)
        for t in range(tiles_per_step):
            offs_i = offs + (i + t) * np.int32(tile_sz)
            nonces = base + jax.lax.bitcast_convert_type(offs_i, jnp.uint32)
            e60, e61 = sym.hash_prepared_e60_e61(prep, nonces)
            digest6 = sym.add(sym.DIGEST6_BIAS, e61)
            hw1 = sym.xor(
                sym.shl(sym.and_(digest6, 0x000000FF), 24),
                sym.shl(sym.and_(digest6, 0x0000FF00), 8),
                sym.shr(sym.and_(digest6, 0x00FF0000), 8),
                sym.shr(sym.and_(digest6, 0xFF000000), 24),
                0x80000000,
            )
            hw1b = jax.lax.bitcast_convert_type(hw1, jnp.int32)
            ok = (e60 == cand_c) & (hw1b <= cap1) & (offs_i < limit)
            any_ok = any_ok | ok
            first_offs = jnp.where(
                ok & (offs_i < first_offs), offs_i, first_offs
            )
        found = jnp.max(any_ok.astype(jnp.int32))
        return (i + tiles_per_step, found, first_offs)

    init = (jnp.int32(0), jnp.int32(0), jnp.full(_TILE, _I32MAX, jnp.int32))
    _, found, first_offs = jax.lax.while_loop(cond, body, init)
    first = jnp.min(first_offs)
    lane = jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    row = jnp.where(lane == np.int32(_FOUND), found, jnp.zeros(_TILE, jnp.int32))
    row = jnp.where(lane == np.int32(_FIRST_IDX), first, row)
    out_ref[0] = jax.lax.bitcast_convert_type(row, jnp.uint32)


@partial(jax.jit, static_argnums=(4, 5))
def pallas_search_candidates_hdr_batch(
    midstates: jnp.ndarray,
    tailws: jnp.ndarray,
    bases: jnp.ndarray,
    valids: jnp.ndarray,
    width: int,
    tiles_per_step: int = 8,
    hw1_cap: jnp.ndarray | None = None,
):
    """Batched twin of :func:`pallas_search_candidates_hdr`: a grid over
    ``B`` roll rows, each sweeping up to ``width`` nonces of ITS OWN
    dynamic header — ``(B, 8)`` midstates, ``(B, 3)`` tail batches
    (``ops.merkle.make_extranonce_roll_batch`` outputs, straight from
    device memory), ``(B,)`` per-row nonce bases and valid counts. One
    dispatch sweeps ``B·width`` global indices; segment boundaries cost
    nothing because they are just row edges of the same launch.

    Returns ``(founds (B,) u32, first_offs (B,) u32)`` — per-row flags
    and lowest candidate offsets (relative to that row's base, valid iff
    the flag is set). Rows are masked to their ``valids`` count exactly
    (a ragged or padding row can never surface an out-of-tile
    candidate), so the caller's cross-row fold is a plain masked min
    over ``global_base[row] + first_offs[row]``.

    Each row hashes through the shared-schedule kernel body (see
    ``_cand_hdr_batch_kernel``): its scalar schedule prefix is hoisted
    out of the tile loop.
    """
    if not 1 <= width <= 1 << 30:
        raise ValueError("width must be in [1, 2^30] (int32 offset domain)")
    if hw1_cap is None:
        hw1_cap = jnp.uint32(0xFFFFFFFF)
    b = midstates.shape[0]
    chunk = _TILE[0] * LANES * tiles_per_step
    n_tiles = -(-width // chunk) * tiles_per_step
    cap_biased = jax.lax.bitcast_convert_type(
        hw1_cap.astype(jnp.uint32) ^ jnp.uint32(0x80000000), jnp.int32
    )
    summary = pl.pallas_call(
        partial(_cand_hdr_batch_kernel, n_tiles, tiles_per_step),
        out_shape=jax.ShapeDtypeStruct((b,) + _TILE, jnp.uint32),
        grid=(b,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 5,
        out_specs=pl.BlockSpec(
            (1,) + _TILE, lambda i: (i, 0, 0), memory_space=pltpu.VMEM
        ),
        interpret=_interpret(),
    )(
        midstates.astype(jnp.uint32),
        tailws.astype(jnp.uint32),
        bases.astype(jnp.uint32),
        valids.astype(jnp.int32),
        cap_biased.reshape(1),
    )
    return summary[:, 0, _FOUND], summary[:, 0, _FIRST_IDX]


# ---------------------------------------------------------------------------
# toy-dialect (MIN) fold kernel
# ---------------------------------------------------------------------------

def _min_kernel(template, n_tiles, tiles_per_step, n_valid,
                base_ref, out_ref):
    """Whole-chunk toy-dialect fold in one invocation: minimize the
    64-bit fold (digest words 0, 1) over ``n_valid`` consecutive 64-bit
    nonces. Same tile/ILP structure as the search kernel, no early exit
    (a min has none)."""
    offs = (
        jax.lax.broadcasted_iota(jnp.int32, _TILE, 0) * np.int32(LANES)
        + jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    )
    base_hi, base_lo = base_ref[0], base_ref[1]
    limit = np.int32(n_valid)
    tile_sz = _TILE[0] * LANES

    def body(i, carry):
        min_hi, min_lo, min_offs = carry
        for t in range(tiles_per_step):
            offs_i = offs + (i + t) * np.int32(tile_sz)
            lo = base_lo + jax.lax.bitcast_convert_type(offs_i, jnp.uint32)
            hi = base_hi + (lo < base_lo).astype(jnp.uint32)  # 64-bit carry
            digest = sym.double_sha256_sym(template, hi, lo)
            fh = jax.lax.bitcast_convert_type(
                sym.xor(digest[0], 0x80000000), jnp.int32
            )
            fl = jax.lax.bitcast_convert_type(
                sym.xor(digest[1], 0x80000000), jnp.int32
            )
            c_lt = (fh < min_hi) | ((fh == min_hi) & (fl < min_lo))
            c_lt = c_lt & (offs_i < limit)
            min_hi = jnp.where(c_lt, fh, min_hi)
            min_lo = jnp.where(c_lt, fl, min_lo)
            min_offs = jnp.where(c_lt, offs_i, min_offs)
        return min_hi, min_lo, min_offs

    init = (
        jnp.full(_TILE, _I32MAX, jnp.int32),
        jnp.full(_TILE, _I32MAX, jnp.int32),
        jnp.full(_TILE, _I32MAX, jnp.int32),
    )
    min_hi, min_lo, min_offs = jax.lax.fori_loop(
        0, n_tiles // tiles_per_step,
        lambda s, c: body(s * tiles_per_step, c), init
    )
    # cross-lane argmin (2 words), lowest-offset tie-break
    m_hi = jnp.min(min_hi)
    mask = min_hi == m_hi
    m_lo = jnp.min(jnp.where(mask, min_lo, _I32MAX))
    mask = mask & (min_lo == m_lo)
    m_off = jnp.min(jnp.where(mask, min_offs, _I32MAX))
    lane = jax.lax.broadcasted_iota(jnp.int32, _TILE, 1)
    unbias = np.int32(-0x80000000)
    row = jnp.zeros(_TILE, jnp.int32)
    for idx, val in ((0, m_hi ^ unbias), (1, m_lo ^ unbias), (2, m_off)):
        row = jnp.where(lane == np.int32(idx), val, row)
    out_ref[...] = jax.lax.bitcast_convert_type(row, jnp.uint32)


@partial(jax.jit, static_argnums=(0, 3, 4))
def pallas_min_toy(
    template: ops.NonceTemplate,
    base_hi: jnp.ndarray,
    base_lo: jnp.ndarray,
    n: int,
    tiles_per_step: int = 8,
):
    """Toy-dialect fold over ``n`` consecutive 64-bit nonces from
    ``(base_hi, base_lo)``: returns ``(fold_hi, fold_lo, argmin_off)`` —
    the minimum ``toy_hash`` value as u32 halves and the offset of its
    nonce. Lanes past ``n`` are masked; ties resolve to the lowest
    nonce."""
    if not 1 <= n <= 1 << 30:
        raise ValueError("n must be in [1, 2^30] (int32 offset domain)")
    chunk = _TILE[0] * LANES * tiles_per_step
    n_tiles = -(-n // chunk) * tiles_per_step
    summary = pl.pallas_call(
        partial(_min_kernel, template, n_tiles, tiles_per_step, n),
        out_shape=jax.ShapeDtypeStruct(_TILE, jnp.uint32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(jnp.stack([base_hi.astype(jnp.uint32).reshape(()),
                 base_lo.astype(jnp.uint32).reshape(())]))
    row = summary[0]
    return row[0], row[1], row[2]
