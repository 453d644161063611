"""Batched on-device extranonce rolling: one dispatch sweeps many rolls.

PR 1 moved the roll itself on device (``ops.merkle``), but the rolled
production path still paid a **host-orchestrated loop per extranonce**:
one synchronous ``roll()`` call per segment, then a fresh
``CandidateSearch`` drained to completion before the next extranonce
started — the depth-2 double buffering died at every segment boundary,
and at test/CI ``nonce_bits`` (≤ 20) the boundary cost dominated. This
module makes the rolled sweep batch- and pipeline-native end to end:

- **Tiles, not segments.** A dispatch window of ``roll_batch × width``
  global indices decomposes into ``chain.rolled_tiles`` — ``(segment,
  base, n)`` rows that never cross an extranonce boundary but whose
  *window* does. ``width`` divides the segment size (both powers of
  two), so a window needs at most ``roll_batch + 2`` rows
  (:func:`plan_tiles` pads to exactly that, keeping every dispatch the
  same compiled shape).
- **One roll call per window.** ``ops.merkle.make_extranonce_roll_batch``
  produces every row's ``(midstate, tail_words)`` in ONE device call;
  the outputs never visit the host.
- **One sweep call per window.** The per-row-midstate candidate sweep
  (``kernels.pallas_search_candidates_hdr_batch`` on TPU, its jnp
  mirror here on the CPU mesh) grids over (roll-row × nonce-slab), so
  one dispatch covers ``roll_batch · width`` global indices.
- **One search for the whole job.** ``search.CandidateSearch`` runs
  over *global* indices (``domain = 2^span_bits``) with windows as its
  slabs — depth-``k`` pipelining now spans segment boundaries, and the
  min-fold/candidate bookkeeping is keyed by global index exactly as
  before.

``roll_batch`` is a size, never a mode: ``roll_batch=1`` is a window of
one row (plus the two rows a misaligned window may straddle), through
the same roll, sweep and search as any other size.

The ``engine`` seam ("pallas" on TPU, "jnp" on the CPU mesh) is what
lets CI pin the whole batched path without a chip. ``cand_bits`` scales
the candidate bar for tests ONLY: production keeps 32 (top hash word
zero + the hash-word-1 cap, the necessary condition at every real
difficulty); tests shrink it so a CI-sized space contains candidates
and the full surfacing/re-issue/min-fold machinery gets exercised at
toy difficulty.
"""

from __future__ import annotations

import struct
from functools import lru_cache, partial
from typing import Callable, Iterator, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpuminter import chain
from tpuminter.ops import sha256 as ops
from tpuminter.protocol import MIN_UNTRACKED, Request, Result
from tpuminter.search import (
    CandidateSearch, pipeline_spans, pull, resolve_handle,
)

__all__ = [
    "plan_tiles",
    "TilePlan",
    "tile_width",
    "span_bits",
    "rolled_verifier",
    "mine_rolled_fast",
    "mine_rolled_tracking",
    "ProgressFn",
    "report_search_progress",
]

_UMAX = np.uint32(0xFFFFFFFF)

#: progress(high_water, best_nonce, best_hash): sub-chunk settled
#: high-water reporting for roll-budget chunks (ISSUE 14). Called at
#: window/segment boundaries from the mining (executor) thread with the
#: highest verifiably-swept GLOBAL index and the running min-fold pair
#: (``MIN_UNTRACKED`` when no candidate surfaced yet). The worker role
#: loop installs one to feed Beacon emission; None (the default
#: everywhere) keeps the paths bit-for-bit on their pre-beacon behavior.
ProgressFn = Callable[[int, int, int], None]


def span_bits(req: Request) -> int:
    """Bit width of a rolled job's global (extranonce × nonce) index
    space — the ``CandidateSearch`` domain (mirrors protocol
    validation)."""
    return min(64, req.nonce_bits + 8 * req.extranonce_size)


def tile_width(nonce_bits: int, cap: int) -> int:
    """Per-row sweep width: the segment size, capped at the largest
    power of two ≤ ``cap``. Power-of-two by construction, so it divides
    the segment size — the invariant :func:`plan_tiles`'s row bound
    rests on."""
    if cap < 1:
        raise ValueError("width cap must be >= 1")
    return min(1 << nonce_bits, 1 << (cap.bit_length() - 1))


class TilePlan(NamedTuple):
    """One dispatch window's rows, padded to a fixed count (host-side
    numpy, ready to feed the batched roll + sweep). ``goffs`` are global
    offsets relative to the window start (u32 — windows are < 2^32 by
    construction); padding rows have ``valids == 0`` and can never
    surface a candidate."""

    en_hi: np.ndarray
    en_lo: np.ndarray
    bases: np.ndarray
    valids: np.ndarray
    goffs: np.ndarray


def plan_tiles(
    start: int,
    n: int,
    nonce_bits: int,
    width: int,
    rows: int,
    hard_end: Optional[int] = None,
    interleave: int = 1,
) -> TilePlan:
    """Decompose the window ``[start, start + n)`` into ≤ ``rows``
    ``chain.rolled_tiles`` rows, padded to exactly ``rows``.

    ``hard_end`` clamps at the index domain's end (oversweep past a
    job's ``upper`` is fine — the search's clean-sweep accounting
    ignores it — but extranonces past the domain don't exist).
    ``interleave=k`` lays rows out device-major for a k-device sharded
    sweep: shard ``d``'s contiguous block holds global-order stripes
    ``{s·k + d}``, so stripe-synchronous early exit stays exact (the
    ``parallel.build_candidate_sweep`` striping argument, row-shaped).
    """
    end = start + n - 1
    if hard_end is not None:
        end = min(end, hard_end)
    if rows % interleave != 0:
        raise ValueError("rows must be a multiple of interleave")
    tiles = list(chain.rolled_tiles(start, end, nonce_bits, width))
    if len(tiles) > rows:
        raise ValueError(
            f"window [{start}, {end}] needs {len(tiles)} rows > {rows}; "
            "width must divide the segment size (tile_width does)"
        )
    en_hi = np.zeros(rows, np.uint32)
    en_lo = np.zeros(rows, np.uint32)
    bases = np.zeros(rows, np.uint32)
    valids = np.zeros(rows, np.uint32)
    goffs = np.zeros(rows, np.uint32)
    for i, (en, base, take, gbase) in enumerate(tiles):
        en_hi[i] = en >> 32
        en_lo[i] = en & 0xFFFFFFFF
        bases[i] = base
        valids[i] = take
        goffs[i] = gbase - start
    if interleave > 1:
        # device-major permutation: new[d·S + s] = old[s·k + d]
        perm = (
            np.arange(rows)
            .reshape(rows // interleave, interleave)
            .T.reshape(-1)
        )
        en_hi, en_lo = en_hi[perm], en_lo[perm]
        bases, valids, goffs = bases[perm], valids[perm], goffs[perm]
    return TilePlan(en_hi, en_lo, bases, valids, goffs)


def lean_plan(plan: TilePlan, rows: int) -> TilePlan:
    """Shape-bucket a padded plan: when the tail rows past ``rows`` are
    all padding (every steady-state aligned window — raggedness only
    appears at job edges and candidate re-issues), slice to the lean
    ``rows``-row shape. Two compiled shapes total, and the common case
    stops paying the pad rows' full-width compute (measured +25% on the
    fixed-shape jnp engine at roll_batch=8)."""
    if plan.valids[rows:].any():
        return plan
    return TilePlan(*(a[:rows] for a in plan))


def rolled_verifier(req: Request):
    """Host-side exact verifier over GLOBAL indices: re-rolls the
    header (LRU per extranonce — a sweep revisits few) and applies the
    full 256-bit compare. The ``CandidateSearch`` ``verify`` callable
    for every batched rolled path."""
    cb = chain.CoinbaseTemplate(
        req.coinbase_prefix, req.coinbase_suffix, req.extranonce_size
    )

    @lru_cache(maxsize=64)
    def prefix76(en: int) -> bytes:
        return chain.rolled_header(req.header, cb, req.branch, en).pack()[:76]

    def verify(g: int) -> Tuple[bool, int]:
        en, nonce = chain.split_global(g, req.nonce_bits)
        h = chain.hash_to_int(
            chain.dsha256(prefix76(en) + struct.pack("<I", nonce))
        )
        return h <= req.target, h

    return verify


def report_search_progress(search: CandidateSearch, fallback_nonce: int,
                           progress: Optional[ProgressFn]) -> None:
    """One :data:`ProgressFn` step for a running global-index
    ``CandidateSearch``: report its settled high-water and running
    min-fold. No-op while nothing is settled or once the search has an
    outcome (a found outcome means a winner sits inside the would-be
    prefix — the final Result covers it). Shared by every batched rolled
    path (here and ``pod_worker``)."""
    if progress is None or search.outcome is not None:
        return
    hw = search.settled_high_water()
    if hw is None:
        return
    cand = search.best_candidate()
    if cand is None:
        progress(hw, fallback_nonce, MIN_UNTRACKED)
    else:
        progress(hw, cand[1], cand[0])


def _resolve_engine(engine: str) -> str:
    if engine == "auto":
        return "jnp" if jax.default_backend() == "cpu" else "pallas"
    if engine not in ("pallas", "jnp"):
        raise ValueError(f"unknown engine {engine!r}")
    return engine


def _check_roll_batch(roll_batch: int) -> None:
    if roll_batch < 1:
        raise ValueError("roll_batch must be >= 1")


# ---------------------------------------------------------------------------
# candidate engines (the fast path's per-dispatch programs)
# ---------------------------------------------------------------------------

def _jnp_candidate_ok(digests, cap, cand_bits: int):
    """The early-reject candidate test, jnp form: top ``cand_bits`` hash
    bits zero (+ the hash-word-1 cap at the production 32). ``cand_bits
    < 32`` is the TEST seam — a CI-sized space then contains candidates
    and the fast path's surfacing machinery is exercisable at toy
    difficulty (soundness needs ``target < 2^(256 - cand_bits)``, which
    those tests arrange exactly as production difficulties do for 32)."""
    hw0 = ops.byteswap32(digests[..., 7])
    if cand_bits == 32:
        hw1 = ops.byteswap32(digests[..., 6])
        return (hw0 == 0) & (hw1 <= cap)
    return (hw0 >> np.uint32(32 - cand_bits)) == 0


def _jnp_candidate_ok_sched(mid, tw, nonces, cap, cand_bits: int):
    """The same candidate test from the shared-schedule truncated hash
    (ISSUE 16): digest word 7 = ``H0[7] + e60`` and word 6 =
    ``DIGEST6_BIAS + e61``, so the two words :func:`_jnp_candidate_ok`
    byteswaps are recovered exactly — same booleans, bit for bit — while
    the sweep skips the final rounds, the a-chain of rounds 58-61, the
    8 digest adds, and the whole (N, 8) digest materialization."""
    from tpuminter.ops import symbolic as sym

    e60, e61 = ops.header_e60_e61_dyn(mid, tw, nonces)
    hw0 = ops.byteswap32(sym.add(e60, int(ops.SHA256_H0[7])))
    if cand_bits == 32:
        hw1 = ops.byteswap32(sym.add(e61, sym.DIGEST6_BIAS))
        return (hw0 == 0) & (hw1 <= cap)
    return (hw0 >> np.uint32(32 - cand_bits)) == 0


def _jnp_batched_candidate_sweep(
    mids, tails, bases, valids, goffs, cap, width: int, cand_bits: int,
):
    """Dispatch :func:`_jnp_batched_sweep` to the program that runs on
    this backend: XLA:CPU's fusion pass merges the unrolled rounds of
    the shared-schedule hash into one loop fusion that recomputes every
    shared subexpression per element, so its run time grows
    exponentially with the round count (jaxlib 0.9: 24 rounds run in
    2 s, 62 did not finish in 300 s). Fusion is switched off for that
    one program on CPU only."""
    sweep = _jnp_batched_sweep
    if jax.default_backend() == "cpu":
        sweep = _jnp_batched_sweep_unfused
    return sweep(mids, tails, bases, valids, goffs, cap, width, cand_bits)


def _jnp_batched_sweep(
    mids, tails, bases, valids, goffs, cap, width: int, cand_bits: int,
):
    """jnp mirror of ``pallas_search_candidates_hdr_batch`` + the
    cross-row fold, one program: (R, width) nonces under R dynamic
    headers → ``[found, first_global_off]``. Compiled once per (width,
    cand_bits) — nothing job-specific is baked.

    Rows run SEQUENTIALLY inside the program (``lax.scan``), mirroring
    the Pallas kernel's grid-over-rows: on the CPU engine a flat
    (R·width)-lane program blows the cache and costs ~50% more per hash
    (measured: 3.15 → 4.86 µs at 8×256), while per-row working sets
    stay cache-sized and the dispatch count still drops ~B×. Each row
    hashes through the shared-schedule truncated form
    (:func:`_jnp_candidate_ok_sched`, measured ~34× per hash over the
    full digest on CPU at 8×256, PERF.md §Round 14)."""
    col = jnp.arange(width, dtype=jnp.uint32)

    def row(carry, x):
        mid, tw, base, valid, goff = x
        ok = _jnp_candidate_ok_sched(mid, tw, base + col, cap, cand_bits)
        ok = ok & (col < valid)
        g = jnp.where(ok, goff + col, _UMAX)
        found, first = carry
        return (found | ok.any(), jnp.minimum(first, jnp.min(g))), None

    (found, first), _ = jax.lax.scan(
        row, (jnp.bool_(False), jnp.uint32(_UMAX)),
        (mids, tails, bases, valids, goffs),
    )
    return jnp.stack([found.astype(jnp.uint32), first])


_jnp_batched_sweep_unfused = jax.jit(
    _jnp_batched_sweep, static_argnums=(6, 7),
    compiler_options={"xla_disable_hlo_passes": "fusion"},
)
_jnp_batched_sweep = jax.jit(_jnp_batched_sweep, static_argnums=(6, 7))


@partial(jax.jit, static_argnums=(6, 7))
def _pallas_batched_candidate_sweep(
    mids, tails, bases, valids, goffs, cap, width: int, tiles_per_step: int,
):
    """Pallas engine: the batched dynamic-header kernel (one launch
    grids over roll rows, each row's schedule prefix hoisted out of the
    tile loop via ``sym.prepare_hdr``) + the same cross-row fold."""
    from tpuminter.kernels import pallas_search_candidates_hdr_batch

    founds, firsts = pallas_search_candidates_hdr_batch(
        mids, tails, bases, valids, width, tiles_per_step, cap
    )
    ok = founds != 0
    g = jnp.where(ok, goffs + firsts, _UMAX)
    return jnp.stack([ok.any().astype(jnp.uint32), jnp.min(g)])


# ---------------------------------------------------------------------------
# fast path: candidate pipeline over global indices
# ---------------------------------------------------------------------------

def _fast_result(req: Request, found, nonce, hash_value, searched, candidates):
    if found:
        return Result(
            req.job_id, req.mode, nonce, hash_value, found=True,
            searched=searched, chunk_id=req.chunk_id,
        )
    best = min(((h, g) for g, h in candidates), default=None)
    hash_value, nonce = best if best else (MIN_UNTRACKED, req.lower)
    return Result(
        req.job_id, req.mode, nonce, hash_value, found=False,
        searched=searched, chunk_id=req.chunk_id,
    )


def mine_rolled_fast(
    req: Request,
    *,
    slab: int = 1 << 27,
    depth: int = 2,
    roll_batch: int = 8,
    engine: str = "auto",
    tiles_per_step: int = 8,
    cand_bits: int = 32,
    progress: Optional[ProgressFn] = None,
) -> Iterator[Optional[Result]]:
    """The production >2^32 search, batched: candidate sweeps over the
    whole rolled range through ONE ``CandidateSearch``, each dispatch
    covering ``roll_batch`` roll rows of ``tile_width(nonce_bits,
    slab)`` nonces (one batched roll call + one batched sweep call per
    window — no header bytes ever cross the host boundary,
    BASELINE.json:9-10).

    The shared-schedule layer (ISSUE 16) is the only body: the sweep
    hashes through the truncated unrolled second compression
    (:func:`_jnp_candidate_ok_sched`, or the kernel's ``sym.prepare_hdr``
    hoist on the Pallas engine) and the batched roll dedupes identical
    extranonce rows before dispatch
    (:func:`tpuminter.ops.merkle.roll_batch_deduped`).

    ``progress`` (:data:`ProgressFn`) receives the settled global-index
    high-water after each resolved window — the roll-budget beacon feed.
    """
    assert req.rolled and req.header is not None and req.target is not None
    _check_roll_batch(roll_batch)
    engine = _resolve_engine(engine)
    from tpuminter.ops import merkle

    hw1_cap = jnp.uint32(int(ops.target_to_words(req.target)[1]))
    width = tile_width(req.nonce_bits, slab)
    rows = roll_batch + 2
    window = roll_batch * width
    if window >= 1 << 32:
        raise ValueError("roll_batch × width must stay below 2^32")
    hard_end = (1 << span_bits(req)) - 1
    roll = merkle.make_extranonce_roll_batch(
        req.header, req.coinbase_prefix, req.coinbase_suffix,
        req.extranonce_size, req.branch,
    )

    def sweep(start: int, n: int, after):
        # the batched kernel does not chain: ``after`` is not read
        plan = lean_plan(
            plan_tiles(start, n, req.nonce_bits, width, rows, hard_end),
            roll_batch,
        )
        mids, tails = merkle.roll_batch_deduped(roll, plan.en_hi, plan.en_lo)
        args = (
            mids, tails, jnp.asarray(plan.bases), jnp.asarray(plan.valids),
            jnp.asarray(plan.goffs), hw1_cap,
        )
        if engine == "pallas":
            return _pallas_batched_candidate_sweep(*args, width, tiles_per_step)
        return _jnp_batched_candidate_sweep(*args, width, cand_bits)

    search = CandidateSearch(
        sweep, resolve_handle, rolled_verifier(req), req.lower, req.upper,
        slab=window, depth=depth, domain=1 << span_bits(req),
    )
    for _ in search.events():
        report_search_progress(search, req.lower, progress)
        yield None  # heartbeat / Cancel window per resolved window
    out = search.outcome
    yield _fast_result(
        req, out.found, out.nonce, out.hash_value, out.searched,
        out.candidates,
    )


# ---------------------------------------------------------------------------
# tracking path: exact exhausted-range minima (CpuMiner-compatible)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnums=(6,))
def _tracking_step(mids, tails, bases, valids, goffs, target_words, width: int):
    """Exact batched rolled step: full digests for every (row, nonce),
    in-program first-winner AND lexicographic-min folds over the masked
    grid. Returns 19 packed u32: ``[found, first_goff, min_goff,
    first_digest×8, min_digest×8]`` — one device array, one pull (the
    ``search.pack_handle`` rule). Ties fold to the lowest global index:
    rows scan in global order (strict-less carry updates keep the
    earlier row) and per-row argmins tie low. Rows run sequentially
    (``lax.scan``) for the same cache reason as the candidate sweep."""
    col = jnp.arange(width, dtype=jnp.uint32)

    def row(carry, x):
        mid, tw, base, valid, goff = x
        digests = ops.header_digest_dyn(mid, tw, base + col)  # (W, 8)
        hw = ops.hash_words_be(digests)
        valid_m = col < valid
        ok = ops.lex_le(hw, target_words) & valid_m
        g = jnp.where(ok, goff + col, _UMAX)
        fidx = jnp.argmin(g)
        masked_hw = jnp.where(valid_m[:, None], hw, _UMAX)
        midx = ops.lex_argmin(masked_hw)
        found, first, first_d, min_hw, min_d, min_g = carry
        take = g[fidx] < first
        first = jnp.where(take, g[fidx], first)
        first_d = jnp.where(take, digests[fidx], first_d)
        row_hw = masked_hw[midx]
        lt = ops.lex_le(row_hw, min_hw) & ~ops.lex_le(min_hw, row_hw)
        min_hw = jnp.where(lt, row_hw, min_hw)
        min_d = jnp.where(lt, digests[midx], min_d)
        min_g = jnp.where(lt, goff + col[midx], min_g)
        return (found | ok.any(), first, first_d, min_hw, min_d, min_g), None

    init = (
        jnp.bool_(False), jnp.uint32(_UMAX), jnp.zeros(8, jnp.uint32),
        jnp.full(8, _UMAX, jnp.uint32), jnp.zeros(8, jnp.uint32),
        jnp.uint32(_UMAX),
    )
    (found, first, first_d, _, min_d, min_g), _ = jax.lax.scan(
        row, init, (mids, tails, bases, valids, goffs)
    )
    return jnp.concatenate([
        jnp.stack([found.astype(jnp.uint32), first, min_g]),
        first_d, min_d,
    ])


def mine_rolled_tracking(
    req: Request,
    *,
    width_cap: int = 1 << 14,
    depth: int = 2,
    roll_batch: int = 8,
    progress: Optional[ProgressFn] = None,
) -> Iterator[Optional[Result]]:
    """Exact rolled search (CpuMiner-compatible first winner AND
    exhausted minimum), batched: windows of ``roll_batch`` roll rows
    with full digests + on-device min folds, pipelined ``depth`` deep
    ACROSS segment boundaries (``search.pipeline_spans`` no longer dies
    at each one). jnp engine — compiles on every backend, one program
    for every job and extranonce (the dynamic-header property); the
    toy-easy-target correctness path plus JaxMiner's production rolled
    path. Pinned to brute force in tests/test_extranonce.py.

    The roll goes through the extranonce dedup
    (:func:`tpuminter.ops.merkle.roll_batch_deduped`); the tracking
    step itself keeps the scanned full-digest compress. Sharing the
    unrolled schedule inside the full-digest + lexicographic-min fold
    was measured and REJECTED — every fold structure tried either lost
    outright or paid a 15-42 s compile per width (PERF.md §Round 14);
    the truncated e60/e61 trick doesn't apply when all 8 digest words
    feed the min fold.
    """
    assert req.rolled and req.target is not None
    _check_roll_batch(roll_batch)
    from tpuminter.ops import merkle

    width = tile_width(req.nonce_bits, width_cap)
    rows = roll_batch + 2
    window = roll_batch * width
    hard_end = (1 << span_bits(req)) - 1
    roll = merkle.make_extranonce_roll_batch(
        req.header, req.coinbase_prefix, req.coinbase_suffix,
        req.extranonce_size, req.branch,
    )
    target_words = jnp.asarray(ops.target_to_words(req.target))

    def dispatch(start: int):
        # exact path: clamp the plan at the job's upper — oversweep
        # lanes must not leak into the min fold
        n = min(window, req.upper - start + 1)
        plan = lean_plan(
            plan_tiles(start, n, req.nonce_bits, width, rows, hard_end),
            roll_batch,
        )
        mids, tails = merkle.roll_batch_deduped(roll, plan.en_hi, plan.en_lo)
        return _tracking_step(
            mids, tails, jnp.asarray(plan.bases), jnp.asarray(plan.valids),
            jnp.asarray(plan.goffs), target_words, width,
        )

    starts = range(req.lower, req.upper + 1, window)
    best: Optional[Tuple[int, int]] = None  # (hash, global index)
    for start, handle in pipeline_spans(starts, dispatch, depth=depth):
        row = pull(handle)
        if int(row[0]):
            g = start + int(row[1])
            h = ops.digest_to_int(row[3:11])
            yield Result(
                req.job_id, req.mode, g, h, found=True,
                searched=g - req.lower + 1, chunk_id=req.chunk_id,
            )
            return
        cand = (ops.digest_to_int(row[11:19]), start + int(row[2]))
        if best is None or cand < best:
            best = cand
        if progress is not None:
            # windows resolve in dispatch order, so the settled prefix
            # ends exactly at this (clamped) window's last index
            progress(min(start + window, req.upper + 1) - 1, best[1], best[0])
        yield None
    yield Result(
        req.job_id, req.mode, best[1], best[0],
        found=best[0] <= req.target,
        searched=req.upper - req.lower + 1, chunk_id=req.chunk_id,
    )
