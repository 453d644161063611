"""1-D nonce mesh: shard_map sweeps with ICI collectives (SURVEY.md §7
stage 5; BASELINE.json:5).

Layout: a sweep covers ``n_batches × batch_per_device`` nonces *per
device*, and device ``d`` owns the contiguous shard starting at
``start + d · n_batches · batch_per_device`` — contiguous per chip, as
the north-star specifies, so a found nonce pins down which chip searched
what without any gather.

Early exit: a ``lax.while_loop`` steps through batches; each iteration
ends with a pod-wide **or-reduce of the found flag over ICI**
(``lax.pmax`` on a u32 flag), so every chip stops within one batch of the
first sub-target hash anywhere on the pod — no host round-trip in the
loop. The winner is folded with a ``pmin`` on the winning nonce plus a
masked ``psum`` to broadcast its digest (disjoint shards ⇒ exactly one
contributor).

Everything compiles under ``jit`` with static shapes; the same code runs
on a real TPU slice and on the fake 8-device CPU mesh CI uses
(tests/conftest.py, ``__graft_entry__.dryrun_multichip``).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from tpuminter.ops import sha256 as ops

__all__ = [
    "make_mesh",
    "build_target_sweep",
    "build_min_fold",
    "build_min_sweep_pallas",
    "build_exact_sweep_pallas",
    "build_candidate_sweep",
    "build_rolled_sweep",
]

AXIS = "nonce"


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: the sweeps'
    collectives produce replicated outputs by construction, and the
    checker does not follow some of the collective patterns used here."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def make_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over all (or the given) devices, axis name "nonce"."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (AXIS,))


def build_target_sweep(
    mesh: Mesh,
    template: ops.NonceTemplate,
    *,
    batch_per_device: int,
    n_batches: int,
) -> Callable:
    """Compile a pod-wide TARGET-mode sweep with EXACT min tracking —
    the pod's ``--exact-min`` engine (PodMiner routes TARGET through it
    when fleets need CpuMiner-compatible exhausted-range minima; the
    fast candidate pipeline tracks minima only when a candidate
    surfaces).

    Returns ``sweep(start_u32, target_words_u32x8, limit_u32) ->
    (found_u32, nonce_u32, digest_words_u32x8, batches_done_u32)`` —
    replicated scalars/vectors, identical on every chip. Nonces past the
    inclusive ``limit`` are masked out of both the winner test and the
    min fold, so a ragged final span stays exact. ``batches_done`` tells
    the host how much of the sweep actually ran (early exit) for
    hash-rate accounting; when nothing is found the digest/nonce outputs
    are the pod-wide exact minimum over the covered (unmasked) nonces.
    """
    n_dev = mesh.devices.size
    per_dev_total = np.uint32(n_batches * batch_per_device)

    def per_device(start: jnp.ndarray, target_words: jnp.ndarray,
                   limit: jnp.ndarray):
        d = lax.axis_index(AXIS).astype(jnp.uint32)
        dev_start = start + d * per_dev_total

        def cond(state):
            b, found, _, _, _ = state
            return (b < n_batches) & (found == 0)

        def body(state):
            b, _, _, _, best = state
            best_words, best_nonce = best
            nonces = (
                dev_start
                + b.astype(jnp.uint32) * np.uint32(batch_per_device)
                + jnp.arange(batch_per_device, dtype=jnp.uint32)
            )
            digests = ops.double_sha256_header_batch(template, nonces)
            hw = ops.hash_words_be(digests)
            # ragged-end mask: out-of-range lanes neither win nor fold.
            # `nonces >= start` kills lanes whose u32 arithmetic wrapped
            # past 2^32 in a top-of-range chunk (they'd otherwise pass
            # the <= limit test with small wrapped values).
            valid = (nonces <= limit) & (nonces >= start)
            hw = jnp.where(valid[:, None], hw, np.uint32(0xFFFFFFFF))
            ok = ops.lex_le(hw, target_words) & valid
            local_found = ok.any()
            first = jnp.argmax(ok)
            # pod-wide or-reduce over ICI: the early-exit signal
            found = lax.pmax(local_found.astype(jnp.uint32), AXIS)
            # winner fold: lowest winning nonce wins; its digest comes via
            # a masked psum (shards are disjoint ⇒ one contributor)
            cand_nonce = jnp.where(local_found, nonces[first], np.uint32(0xFFFFFFFF))
            win_nonce = lax.pmin(cand_nonce, AXIS)
            is_winner = local_found & (cand_nonce == win_nonce)
            win_digest = lax.psum(
                jnp.where(is_winner, digests[first], np.uint32(0)), AXIS
            )
            # best-effort min fold (for the exhausted case): local lex-min
            # this batch vs carried best, in hash-value word order
            midx = ops.lex_argmin(hw)
            batch_best_words = hw[midx]
            batch_best_nonce = nonces[midx]
            keep = ops.lex_le(best_words, batch_best_words)
            new_best_words = jnp.where(keep, best_words, batch_best_words)
            new_best_nonce = jnp.where(keep, best_nonce, batch_best_nonce)
            return (
                b + 1,
                found,
                win_nonce,
                win_digest,
                (new_best_words, new_best_nonce),
            )

        init = (
            jnp.uint32(0),
            jnp.uint32(0),
            jnp.uint32(0xFFFFFFFF),
            jnp.zeros(8, dtype=jnp.uint32),
            (jnp.full(8, 0xFFFFFFFF, dtype=jnp.uint32), jnp.uint32(0)),
        )
        b, found, win_nonce, win_digest, (best_words, best_nonce) = lax.while_loop(
            cond, body, init
        )
        # exhausted: fold the per-device best across the pod. all_gather
        # of 8 u32 per chip is trivial ICI traffic; argmin on-replica.
        all_words = lax.all_gather(best_words, AXIS)      # (n_dev, 8)
        all_nonces = lax.all_gather(best_nonce, AXIS)     # (n_dev,)
        bi = ops.lex_argmin(all_words)
        # hash words (msb-first) → digest words for uniform host decoding
        fallback_digest = ops.hash_words_be(all_words[bi])
        nonce_out = jnp.where(found > 0, win_nonce, all_nonces[bi])
        digest_out = jnp.where(found > 0, win_digest, fallback_digest)
        return found, nonce_out, digest_out, b

    sharded = _shard_map(
        per_device, mesh, in_specs=(P(), P(), P()), out_specs=(P(), P(), P(), P())
    )
    return jax.jit(sharded)


def build_min_sweep_pallas(
    mesh: Mesh,
    template: ops.NonceTemplate,
    *,
    slab_per_device: int,
    tiles_per_step: int = 8,
) -> Callable:
    """Compile the PRODUCTION pod-wide MIN-mode (toy dialect) step: each
    chip folds its contiguous ``slab_per_device`` 64-bit nonces through
    the fused Pallas toy kernel (``kernels.pallas_min_toy`` — the same
    engine the single-chip TpuMiner runs, VERDICT r3 weak #3), then the
    per-chip ``(fold, argmin)`` candidates fold over ICI.

    Returns ``step(start_hi_u32, start_lo_u32) -> (fold_hi, fold_lo,
    nonce_hi, nonce_lo)`` — replicated. FULL spans only (the Pallas
    kernel's lane mask is static): the host runs ragged tails through
    the single-chip kernel. The jnp ``build_min_fold`` remains the CPU-
    mesh/CI engine (dynamic limit masking, small batches).
    """
    from tpuminter.kernels import pallas_min_toy

    def per_device(start_hi, start_lo):
        d = lax.axis_index(AXIS).astype(jnp.uint32)
        base_lo = start_lo + d * np.uint32(slab_per_device)
        base_hi = start_hi + (base_lo < start_lo).astype(jnp.uint32)
        fh, fl, off = pallas_min_toy(
            template, base_hi, base_lo, slab_per_device, tiles_per_step
        )
        n_lo = base_lo + off.astype(jnp.uint32)
        n_hi = base_hi + (n_lo < base_lo).astype(jnp.uint32)
        fold = jnp.stack([fh, fl])
        all_fold = lax.all_gather(fold, AXIS)     # (n_dev, 2)
        all_hi = lax.all_gather(n_hi, AXIS)
        all_lo = lax.all_gather(n_lo, AXIS)
        bi = ops.lex_argmin(all_fold)
        return all_fold[bi][0], all_fold[bi][1], all_hi[bi], all_lo[bi]

    sharded = _shard_map(
        per_device, mesh, in_specs=(P(), P()), out_specs=(P(), P(), P(), P())
    )
    return jax.jit(sharded)


def build_exact_sweep_pallas(
    mesh: Mesh,
    template: ops.NonceTemplate,
    target_words: Sequence[int],
    *,
    slab_per_device: int,
    tiles_per_step: int = 8,
) -> Callable:
    """Compile the PRODUCTION pod-wide exact-min TARGET step: each chip
    folds its contiguous ``slab_per_device`` nonces through the fused
    tracking kernel (``kernels.pallas_search_target`` — full in-kernel
    256-bit compare plus the running lexicographic-min fold, the same
    engine the single-chip ``--exact-min`` path runs), then the per-chip
    winner/minimum candidates fold over ICI. This is the
    ``build_min_sweep_pallas``/``build_min_fold`` split applied to
    exact-min (VERDICT r5 weak #1: the jnp ``build_target_sweep`` body
    at 2^16-nonce batches left the pod ~1000× below the chip's
    demonstrated tracking-kernel rate).

    Returns ``sweep(start_u32) -> (11,) u32`` — ONE replicated device
    array per call (resolving scalars separately costs one round
    trip each; cf. ``search.pack_handle``), laid out as
    ``[found, win_nonce, min_hash_words×8, min_nonce]``:

    - ``found != 0`` iff some chip's slab contains ``hash <= target``;
      ``win_nonce`` is then the lowest winning nonce *among the chips'
      in-kernel first hits* (each chip early-exits its own slab, so as
      in ``build_target_sweep`` a later chip's hit ends the sweep while
      lower unswept nonces wait for the host's next span — the host
      loop resolves spans in order, preserving the per-span-granular
      lowest-winner contract the jnp path has).
    - otherwise ``min_hash_words`` (msb-first hash-value words) /
      ``min_nonce`` are the pod-wide EXACT minimum over the whole
      ``n_dev × slab_per_device`` span.

    FULL spans only (the kernel specializes on ``n`` at compile time):
    the host runs ragged tails through the single-chip kernel, exactly
    like the MIN pallas path. ``target_words`` are baked static (the
    tracking kernel folds the compare into the instruction stream), so
    one compile serves one (header, target) pair — exact-min fleets
    mine one job at a time, where that is the right trade.
    """
    from tpuminter.kernels import pallas_search_target

    tw = tuple(int(t) for t in target_words)
    umax = np.uint32(0xFFFFFFFF)

    def per_device(start):
        d = lax.axis_index(AXIS).astype(jnp.uint32)
        base = start + d * np.uint32(slab_per_device)
        found, first, min_words, min_off = pallas_search_target(
            template, tw, base, slab_per_device, tiles_per_step
        )
        # winner fold: lowest first-hit nonce among this sweep's finders
        cand = jnp.where(found > 0, base + first, umax)
        pod_found = lax.pmax(found, AXIS)
        win_nonce = lax.pmin(cand, AXIS)
        # exact-min fold: all_gather of 9 u32 per chip is trivial ICI
        # traffic; lexicographic argmin on-replica
        all_words = lax.all_gather(min_words, AXIS)        # (n_dev, 8)
        all_nonces = lax.all_gather(base + min_off, AXIS)  # (n_dev,)
        bi = ops.lex_argmin(all_words)
        return jnp.concatenate([
            pod_found.reshape(1),
            win_nonce.reshape(1),
            all_words[bi],
            all_nonces[bi].reshape(1),
        ])

    sharded = _shard_map(
        per_device, mesh, in_specs=(P(),), out_specs=P()
    )
    return jax.jit(sharded)


def build_candidate_sweep(
    mesh: Mesh,
    template: ops.NonceTemplate,
    *,
    slab_per_device: int,
    n_slabs: int,
    tiles_per_step: int = 8,
    kernel: str = "auto",
) -> Callable:
    """Compile the PRODUCTION pod-wide candidate sweep (BASELINE.json:5;
    VERDICT r2 #3): the same early-reject candidate test the single-chip
    hot path runs (``kernels.pallas_search_candidates``), distributed
    over the mesh with a pod-wide **ICI or-reduce** between slabs so
    every chip stops within one slab of the first candidate anywhere.

    **Slab striping.** Work is assigned round-robin at slab granularity:
    in stripe ``b`` device ``d`` sweeps the contiguous slab starting at
    ``start + (b·n_dev + d)·slab_per_device``. Each chip's unit of work
    stays a contiguous multi-million-nonce slab (the north-star's
    contiguous-shard intent), but successive stripes interleave across
    the pod — that is what makes the early exit *exact*: when the
    or-reduce fires at stripe ``b``, every slab in stripes ``< b`` was
    fully swept on some chip, and within stripe ``b`` each chip swept
    up to its own first candidate, so the ``pmin`` of stripe-``b``
    candidates is the lowest candidate in the covered prefix and every
    nonce below it is provably candidate-free. With whole-range
    contiguous shards that claim would be false (a lower chip could
    still be mid-shard when a higher chip hits), and the exact
    lowest-winner contract ``search.CandidateSearch`` depends on would
    break.

    Returns ``sweep(start_u32, cap_biased_i32) -> (found_u32,
    first_off_u32, stripes_done_u32)`` — replicated scalars.
    ``first_off`` is the lowest candidate's offset FROM ``start``
    (valid iff ``found``) — offsets, not absolute nonces, so the fold
    order stays correct when a dispatched span wraps past 2^32 (a
    wrapped absolute nonce would compare below in-range ones) and a
    candidate at nonce 0xFFFFFFFF cannot collide with the not-found
    sentinel (``found`` travels as its own flag). ``cap_biased`` is
    the sign-biased hash-word-1 cap (see
    ``kernels.pallas_search_candidates``). The whole call covers
    ``n_dev × n_slabs × slab_per_device`` consecutive nonces from
    ``start`` with at most ``n_slabs`` ICI round-trips and ZERO host
    syncs.

    ``kernel`` selects the per-slab engine: ``"pallas"`` (the fused
    candidate kernel — the production TPU path), ``"jnp"`` (same
    candidate condition via the jnp ops — compiles on the CPU mesh, the
    CI path), or ``"auto"`` (pallas iff the default backend is not
    CPU). Rolled jobs take :func:`build_rolled_sweep` instead.
    """
    if kernel == "auto":
        kernel = "jnp" if jax.default_backend() == "cpu" else "pallas"
    if kernel not in ("pallas", "jnp"):
        raise ValueError(f"unknown kernel {kernel!r}")
    n_dev = mesh.devices.size
    slab = slab_per_device
    umax = np.uint32(0xFFFFFFFF)

    if kernel == "pallas":
        from tpuminter.kernels import pallas_search_candidates

        def slab_sweep(base, cap_biased):
            cap = jax.lax.bitcast_convert_type(
                cap_biased, jnp.uint32
            ) ^ jnp.uint32(0x80000000)
            return pallas_search_candidates(
                template, base, slab, tiles_per_step, cap
            )
    else:

        def slab_sweep(base, cap_biased):
            nonces = base + jnp.arange(slab, dtype=jnp.uint32)
            digests = ops.double_sha256_header_batch(template, nonces)
            hw = ops.hash_words_be(digests)
            hw1b = jax.lax.bitcast_convert_type(
                hw[:, 1] ^ jnp.uint32(0x80000000), jnp.int32
            )
            ok = (hw[:, 0] == 0) & (hw1b <= cap_biased)
            return ok.any().astype(jnp.uint32), jnp.argmax(ok).astype(jnp.uint32)

    def per_device(start, cap_biased):
        d = lax.axis_index(AXIS).astype(jnp.uint32)

        def cond(state):
            b, found, _ = state
            return (b < n_slabs) & (found == 0)

        def body(state):
            b, _, _ = state
            slab_idx = b * np.uint32(n_dev) + d
            base = start + slab_idx * np.uint32(slab)
            f, off = slab_sweep(base, cap_biased)
            local = (f > 0) & (off < slab)
            cand_off = slab_idx * np.uint32(slab) + off.astype(jnp.uint32)
            # pod-wide or-reduce over ICI: the early-exit signal; pmin
            # folds the stripe's lowest candidate offset in the same
            # round (offsets, not absolute nonces — see docstring)
            found = lax.pmax(local.astype(jnp.uint32), AXIS)
            first = lax.pmin(jnp.where(local, cand_off, umax), AXIS)
            return b + 1, found, first

        b, found, first = lax.while_loop(
            cond, body, (jnp.uint32(0), jnp.uint32(0), umax)
        )
        return found, first, b

    sharded = _shard_map(
        per_device, mesh, in_specs=(P(), P()), out_specs=(P(), P(), P())
    )

    def pod_candidate_sweep(start, cap_biased):
        # the program's own name in a device trace (``XLA Modules`` shows
        # ``jit_pod_candidate_sweep``), apart from the other pod programs
        return sharded(start, cap_biased)

    return jax.jit(pod_candidate_sweep)


def build_rolled_sweep(
    mesh: Mesh,
    *,
    width: int,
    rows: int,
    tiles_per_step: int = 8,
    kernel: str = "auto",
    cand_bits: int = 32,
) -> Callable:
    """Compile the pod-wide BATCHED rolled candidate sweep
    (``tpuminter.rolled`` at slice scale): one call sweeps ``rows`` roll
    ROWS — ``chain.rolled_tiles`` of a global window, each row up to
    ``width`` nonces of its own extranonce's header — sharded over the
    mesh, with the same stripe-synchronous ICI or-reduce early exit as
    :func:`build_candidate_sweep`.

    **Row striping.** ``rows`` must be a multiple of ``n_dev``; the
    caller lays rows out device-major (``rolled.plan_tiles(...,
    interleave=n_dev)``) so that stripe ``s`` = global-order rows
    ``[s·n_dev, (s+1)·n_dev)``, one per device. When the or-reduce
    fires at stripe ``s``, every row in earlier stripes was fully swept
    and within stripe ``s`` each device swept up to its own first
    candidate — so the ``pmin`` over per-row global offsets is the
    lowest candidate in the covered prefix, the exact-lowest-winner
    claim ``search.CandidateSearch`` depends on (the slab-striping
    argument of :func:`build_candidate_sweep`, row-shaped).

    Returns ``sweep(midstates (rows, 8), tailws (rows, 3), bases (rows,),
    valids (rows,), goffs (rows,), cap_biased) -> (found_u32,
    first_goff_u32, stripes_done_u32)`` — replicated scalars;
    ``first_goff`` is the lowest candidate's GLOBAL offset from the
    window start (valid iff ``found``). Rows are masked to their
    ``valids`` exactly: an over-swept or padding row can report a
    candidate past its valid count, which the fold drops — sound,
    because the row's valid prefix was then swept clean. Nothing
    job-specific is baked: one compiled program serves every job and
    every extranonce (``cand_bits`` is the jnp engine's test seam, 32 =
    production).
    """
    from tpuminter.rolled import _jnp_candidate_ok, _resolve_engine

    kernel = _resolve_engine(kernel)
    n_dev = mesh.devices.size
    if rows % n_dev != 0:
        raise ValueError(f"rows {rows} must be a multiple of n_dev {n_dev}")
    rows_pd = rows // n_dev
    umax = np.uint32(0xFFFFFFFF)

    if kernel == "pallas":
        if cand_bits != 32:
            raise ValueError("cand_bits is a jnp-engine test seam only")
        from tpuminter.kernels import pallas_search_candidates_hdr

        def row_sweep(mid, tw, base, cap_biased):
            cap = jax.lax.bitcast_convert_type(
                cap_biased, jnp.uint32
            ) ^ jnp.uint32(0x80000000)
            return pallas_search_candidates_hdr(
                mid, tw, base, width, tiles_per_step, cap
            )
    else:

        def row_sweep(mid, tw, base, cap_biased):
            nonces = base + jnp.arange(width, dtype=jnp.uint32)
            digests = ops.header_digest_dyn(mid, tw, nonces)
            # one source of truth for the candidate bar: un-bias the
            # cap back to u32 and apply tpuminter.rolled's test
            cap = jax.lax.bitcast_convert_type(
                cap_biased, jnp.uint32
            ) ^ jnp.uint32(0x80000000)
            ok = _jnp_candidate_ok(digests, cap, cand_bits)
            return ok.any().astype(jnp.uint32), jnp.argmax(ok).astype(jnp.uint32)

    def per_device(mids, tails, bases, valids, goffs, cap_biased):
        def cond(state):
            s, found, _ = state
            return (s < rows_pd) & (found == 0)

        def body(state):
            s, _, _ = state
            mid = lax.dynamic_index_in_dim(mids, s, 0, keepdims=False)
            tw = lax.dynamic_index_in_dim(tails, s, 0, keepdims=False)
            base = lax.dynamic_index_in_dim(bases, s, 0, keepdims=False)
            valid = lax.dynamic_index_in_dim(valids, s, 0, keepdims=False)
            goff = lax.dynamic_index_in_dim(goffs, s, 0, keepdims=False)
            f, off = row_sweep(mid, tw, base, cap_biased)
            local = (f > 0) & (off < valid)
            cand = jnp.where(local, goff + off, umax)
            # pod-wide or-reduce over ICI: the early-exit signal; pmin
            # folds the stripe's lowest GLOBAL candidate offset in the
            # same round
            found = lax.pmax(local.astype(jnp.uint32), AXIS)
            first = lax.pmin(cand, AXIS)
            return s + 1, found, first

        s, found, first = lax.while_loop(
            cond, body, (jnp.int32(0), jnp.uint32(0), umax)
        )
        return found, first, s.astype(jnp.uint32)

    sharded = _shard_map(
        per_device, mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS), P()),
        out_specs=(P(), P(), P()),
    )
    return jax.jit(sharded)


def build_scrypt_sweep(
    mesh: Mesh,
    *,
    batch_per_device: int,
    n_log2: int = 10,
) -> Callable:
    """Compile a pod-wide SCRYPT-mode batch step (BASELINE.json:11 at
    slice scale): device ``d`` hashes the contiguous batch starting at
    ``start + d · batch_per_device`` through the jnp scrypt pipeline
    (``ops.scrypt.scrypt_header_batch`` — header words are runtime
    values, one compile serves every job and extranonce), then the pod
    folds a winner flag (or-reduce), the first winning nonce (pmin),
    and the running lexicographic minimum (all_gather + argmin) over
    ICI.

    Returns ``step(header76w_u32x19, start_u32, target_words_u32x8) ->
    (found_u32, win_nonce_u32, win_digest_u32x8, min_digest_u32x8,
    min_nonce_u32)`` — replicated. The host loops steps across a chunk
    (scrypt has no candidate trick: the full hash is the test, so each
    step is an exact sweep of ``n_dev × batch_per_device`` nonces).
    Memory: ``batch_per_device × 128·2^n_log2`` bytes of V per chip.
    """

    def per_device(hw19, start, target_words):
        from tpuminter.ops import scrypt as scrypt_ops

        d = lax.axis_index(AXIS).astype(jnp.uint32)
        nonces = (
            start + d * np.uint32(batch_per_device)
            + jnp.arange(batch_per_device, dtype=jnp.uint32)
        )
        digests = scrypt_ops.scrypt_header_batch(hw19, nonces, n_log2)
        hw = ops.hash_words_be(digests)
        ok = ops.lex_le(hw, target_words)
        local_found = ok.any()
        first = jnp.argmax(ok)
        found = lax.pmax(local_found.astype(jnp.uint32), AXIS)
        cand = jnp.where(local_found, nonces[first], np.uint32(0xFFFFFFFF))
        win_nonce = lax.pmin(cand, AXIS)
        is_winner = local_found & (cand == win_nonce)
        win_digest = lax.psum(
            jnp.where(is_winner, digests[first], np.uint32(0)), AXIS
        )
        midx = ops.lex_argmin(hw)
        all_words = lax.all_gather(hw[midx], AXIS)       # (n_dev, 8)
        all_digests = lax.all_gather(digests[midx], AXIS)
        all_nonces = lax.all_gather(nonces[midx], AXIS)
        bi = ops.lex_argmin(all_words)
        return found, win_nonce, win_digest, all_digests[bi], all_nonces[bi]

    sharded = _shard_map(
        per_device, mesh, in_specs=(P(), P(), P()), out_specs=(P(),) * 5
    )
    return jax.jit(sharded)


def build_min_fold(
    mesh: Mesh,
    template: ops.NonceTemplate,
    *,
    batch_per_device: int,
) -> Callable:
    """Compile a pod-wide MIN-mode (toy dialect) batch step.

    Returns ``step(start_hi_u32, start_lo_u32, limit_hi_u32,
    limit_lo_u32) -> (fold_hi, fold_lo, nonce_hi, nonce_lo)`` — the
    pod-wide minimum toy fold over ``n_dev × batch_per_device``
    consecutive nonces from the 64-bit ``start``, device d owning the
    contiguous shard ``start + d · batch_per_device``. Nonces past the
    64-bit ``limit`` (inclusive) are masked out of the fold, so a
    ragged final step stays exact. Host loops this step across a chunk
    and folds (the toy dialect has no early exit to stop for).
    """

    def per_device(start_hi, start_lo, limit_hi, limit_lo):
        d = lax.axis_index(AXIS).astype(jnp.uint32)
        base_lo = start_lo + d * np.uint32(batch_per_device)
        carry = (base_lo < start_lo).astype(jnp.uint32)
        base_hi = start_hi + carry
        offs = jnp.arange(batch_per_device, dtype=jnp.uint32)
        lo = base_lo + offs
        hi = base_hi + (lo < base_lo).astype(jnp.uint32)
        digests = ops.sha256_batch(template, hi, lo)
        fold = digests[:, :2]  # (N, 2): toy fold (hi, lo) words
        over = (hi > limit_hi) | ((hi == limit_hi) & (lo > limit_lo))
        fold = jnp.where(over[:, None], np.uint32(0xFFFFFFFF), fold)
        idx = ops.lex_argmin(fold)
        # pod fold: gather each device's (fold, nonce) candidate
        all_fold = lax.all_gather(fold[idx], AXIS)            # (n_dev, 2)
        all_hi = lax.all_gather(hi[idx], AXIS)
        all_lo = lax.all_gather(lo[idx], AXIS)
        bi = ops.lex_argmin(all_fold)
        return all_fold[bi][0], all_fold[bi][1], all_hi[bi], all_lo[bi]

    sharded = _shard_map(
        per_device, mesh, in_specs=(P(), P(), P(), P()), out_specs=(P(), P(), P(), P())
    )
    return jax.jit(sharded)
