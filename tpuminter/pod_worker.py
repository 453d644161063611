"""PodMiner: one Worker driving a whole TPU slice (BASELINE.json:5).

The north-star's end state: the coordinator keeps handing out nonce
ranges over the control plane, and ONE worker process Joins per slice,
sharding each chunk across its chips via ``shard_map`` with the found-
flag or-reduce riding ICI (``parallel.build_candidate_sweep``). The
role layer cannot tell a PodMiner from a CpuMiner — same ``Miner``
generator contract, same Join/Request/Result messages; only the
``lanes`` hint (scaled by device count) tells the scheduler to carve
pod-sized chunks.

Dialect routing:

- **TARGET** (plain and extranonce-rolled) is the production path:
  ``search.CandidateSearch`` pipelines pod-wide sweeps ``depth`` deep,
  each covering ``n_dev × n_slabs × slab_per_device`` nonces with
  in-kernel early exit per chip — the host only verifies the
  ~1-per-2^32 candidates. A plain job's stripe is one slab split over
  the chips, ``slab_per_device / n_dev`` contiguous nonces each, so a
  winner in a call's first slab stops it once every chip has swept its
  piece, not a whole slab; a call makes up to ``n_slabs × n_dev`` ICI
  rounds. A slab that does not split evenly keeps one slab a chip a
  stripe. Rolled jobs use
  the dynamic-header sweep (one compile for every extranonce) with the
  roll itself on device (``ops.merkle.extranonce_roll``).
- **MIN** runs the fused Pallas toy kernel per chip under ``shard_map``
  (``parallel.build_min_sweep_pallas`` — the single-chip TpuMiner's
  engine at pod scale) with the argmin fold over ICI; the CPU mesh (CI)
  keeps the jnp ``parallel.build_min_fold`` path. Ragged tails run the
  single-chip kernel.
- **exact_min** (``--exact-min``): TARGET chunks track the pod-wide
  EXACT exhausted-range minimum (CpuMiner-compatible) at full-digest
  rates instead of the faster candidate test. Production runs the fused
  tracking kernel per chip under ``shard_map``
  (``parallel.build_exact_sweep_pallas`` — ``pallas_search_target`` at
  slab scale, host loop double-buffered ``depth`` deep); the CPU mesh
  (CI) keeps the jnp ``parallel.build_target_sweep`` with its dynamic
  limit masking.
- **SCRYPT** shards data-parallel over the mesh
  (``parallel.build_scrypt_sweep``): each chip hashes a contiguous
  batch through the jnp scrypt pipeline (ROMix is HBM-bound per chip,
  so per-chip batches saturate per-chip bandwidth and chips scale
  linearly), with winner/min folds over ICI; ragged tails run through
  the single-chip path.

Like TpuMiner's fast path, exhausted TARGET ranges report the exact
minimum only when a candidate surfaced (``protocol.MIN_UNTRACKED``
otherwise — see tpu_worker.py's rationale).
"""

from __future__ import annotations

import struct
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpuminter import chain
from tpuminter.ops import sha256 as ops
from tpuminter.parallel import (
    build_candidate_sweep,
    build_exact_sweep_pallas,
    build_min_fold,
    build_min_sweep_pallas,
    build_target_sweep,
    make_mesh,
)
from tpuminter.protocol import MIN_UNTRACKED, PowMode, Request, Result
from tpuminter.search import (
    CandidateSearch,
    pack_handle,
    pipeline_spans,
    pull,
    resolve_handle,
)
from tpuminter.worker import Miner

__all__ = ["PodMiner", "follower_loop"]


def follower_loop(miner: "PodMiner") -> None:
    """Follower-process main (multi-host pod, ``jax.process_index() !=
    0``): replay the leader's device-program sequence without touching
    the control plane. Each broadcast request is mined with the same
    deterministic generator the leader runs; a 0 step-flag means the
    leader abandoned the chunk (Cancel). Returns on the empty-request
    stop signal (leader shutdown)."""
    from tpuminter.parallel import distributed as dist
    from tpuminter.protocol import decode_msg

    while True:
        raw = dist.broadcast_bytes(None)
        if not raw:
            return
        inner = miner._mine_impl(decode_msg(raw))
        while True:
            if dist.broadcast_flag(None) == 0:
                inner.close()
                break
            try:
                next(inner)
            except StopIteration:
                break

#: defaults sized for v5e chips (cf. tpu_worker.DEFAULT_SLAB): a slab
#: of 2^27 nonces, 4 slabs a chip per pod call (2^31 nonces on four
#: chips). A TARGET stripe is one slab across the pod, 2^25 nonces ≈
#: 33 ms a chip on four chips, so a call makes 16 ICI rounds
DEFAULT_SLAB_PER_DEVICE = 1 << 27
DEFAULT_N_SLABS = 4


def _hash_words_to_int(words) -> int:
    """msb-first u32 hash-value words → the 256-bit hash integer (the
    tracking kernel's min_words layout, kernels.pallas_search_target)."""
    value = 0
    for w in words:
        value = (value << 32) | int(w)
    return value


def _biased_cap(target: int) -> jnp.ndarray:
    """Target's hash-word-1 as the kernels' sign-biased i32 cap."""
    cap = np.uint32(int(ops.target_to_words(target)[1]))
    return jax.lax.bitcast_convert_type(
        jnp.uint32(cap ^ np.uint32(0x80000000)), jnp.int32
    )


class PodMiner(Miner):
    """Whole-slice miner behind the standard Worker interface."""

    backend = "pod"

    def __init__(
        self,
        mesh=None,
        slab_per_device: int = DEFAULT_SLAB_PER_DEVICE,
        n_slabs: int = DEFAULT_N_SLABS,
        depth: int = 2,
        kernel: str = "auto",
        lanes: Optional[int] = None,
        tiles_per_step: int = 8,
        exact_min: bool = False,
        spmd_leader: bool = False,
        scrypt_batch: Optional[int] = None,
        roll_batch: int = 8,
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_dev = int(self.mesh.devices.size)
        self.slab_per_device = slab_per_device
        self.n_slabs = n_slabs
        #: the TARGET sweep's layout (module docstring): each chip's
        #: piece of a stripe and the stripes a call, one slab a stripe
        #: where the slab splits evenly over the chips
        if slab_per_device % self.n_dev == 0:
            self.stripe_slab = slab_per_device // self.n_dev
            self.stripes = n_slabs * self.n_dev
        else:
            self.stripe_slab, self.stripes = slab_per_device, n_slabs
        self.pod_span = self.n_dev * self.stripes * self.stripe_slab
        if self.pod_span > 1 << 32:
            raise ValueError(
                "pod span exceeds the 32-bit nonce space; shrink "
                "slab_per_device or n_slabs"
            )
        # Gloo (the multiprocess CPU mesh's collective transport) cannot
        # disambiguate collectives from two concurrently in-flight
        # programs: depth≥2 pipelining deadlocks or cross-matches frames
        # (observed on jaxlib 0.4.37 — gloo preamble mismatches / hung
        # shutdown barriers in tests/test_distributed.py). Serialize
        # spans there; real TPU runtimes run queued programs in order on
        # one stream, so production keeps the pipeline.
        if depth > 1 and jax.process_count() > 1 and \
                jax.default_backend() == "cpu":
            depth = 1
        self.depth = depth
        self.kernel = kernel
        self.tiles_per_step = tiles_per_step
        # scheduler hint: a pod advertises per-chip throughput × chips,
        # floored at one lane per chip (tiny test slabs underflow the
        # integer division to 0, which the coordinator would clamp to a
        # single-CPU-sized hint)
        self.lanes = (
            lanes if lanes is not None
            else max(self.n_dev, self.n_dev * (slab_per_device * 4) // 16_384)
        )
        self.exact_min = exact_min
        #: per-chip scrypt batch override (default: the measured-optimal
        #: 16384 on TPU / 64 on the CPU mesh); tests shrink it so a
        #: bit-exact host cross-check stays affordable
        self.scrypt_batch = scrypt_batch
        self.span = self.pod_span
        #: multi-host mode: this process is the control-plane leader and
        #: mirrors its request/step stream to follower processes (see
        #: module docstring of ``parallel.distributed``)
        self.spmd_leader = spmd_leader
        self._open_inner = None  # leader's in-progress chunk generator
        #: extranonce rows per rolled dispatch (tpuminter.rolled),
        #: rounded up to a whole number of per-device stripes
        self.roll_batch = roll_batch
        #: jnp-engine candidate-bar seam (tpuminter.rolled docstring):
        #: production 32; tests shrink it so CI-sized rolled spaces
        #: contain candidates
        self._cand_bits = 32
        self._rolled_sweeps = {}  # (width, rows) -> compiled pod sweep
        self._sweep_static = None  # compiled pod programs, built lazily
        self._scrypt_sweep = None
        self._exact_sweep = None
        self._exact_template = None
        self._exact_pallas = None  # compiled (header, target) exact sweep
        self._exact_pallas_key = None
        self._min_sweep = None
        self._min_template = None
        self._fold = None
        self._fold_template = None
        self._template = None
        self._jax_delegate = None

    # -- Miner interface ---------------------------------------------------

    def mine(self, request: Request) -> Iterator[Optional[Result]]:
        if self.spmd_leader:
            yield from self._spmd_mine(request)
        else:
            yield from self._mine_impl(request)

    def _mine_impl(self, request: Request) -> Iterator[Optional[Result]]:
        from tpuminter.tpu_worker import _fast_path_ok

        if request.mode == PowMode.MIN:
            yield from self._mine_min(request)
        elif request.mode == PowMode.SCRYPT:
            yield from self._mine_scrypt(request)
        elif self.exact_min and not request.rolled:
            # CpuMiner-compatible exhausted minima at full-digest rates
            yield from self._mine_target_exact(request)
        elif not _fast_path_ok(request.target):
            # toy-easy targets (≥ 2^224): the candidate test is not a
            # necessary condition there, and a winner lands every few
            # thousand nonces — one chip answers in microseconds, a pod
            # adds nothing. Not the pod's production regime.
            yield from self._easy_delegate(request)
        elif request.rolled:
            yield from self._mine_rolled(request)
        else:
            yield from self._mine_target(request)

    # -- multi-host SPMD mirroring (leader side) ---------------------------

    def _spmd_sync_abandoned(self) -> None:
        """If the previous chunk's generator was abandoned (Cancel), the
        followers are still waiting for its next step flag: release them
        before anything else is broadcast (cf. ProfiledMiner's abandoned-
        trace dance — same generator-contract consequence)."""
        from tpuminter.parallel import distributed as dist

        if self._open_inner is not None:
            inner, self._open_inner = self._open_inner, None
            dist.broadcast_flag(0)
            inner.close()

    def _spmd_mine(self, request: Request) -> Iterator[Optional[Result]]:
        """Leader-side wrapper: broadcast the request, then a liveness
        flag before every generator step, so follower processes replay
        the identical device-program sequence. The inner generator is
        deterministic given the request (replicated outputs drive the
        host loop), so both sides hit StopIteration on the same step —
        flags exist solely for early abandonment."""
        from tpuminter.parallel import distributed as dist
        from tpuminter.protocol import encode_msg

        self._spmd_sync_abandoned()
        inner = self._mine_impl(request)
        self._open_inner = inner
        dist.broadcast_bytes(encode_msg(request))
        try:
            while True:
                dist.broadcast_flag(1)
                try:
                    item = next(inner)
                except StopIteration:
                    self._open_inner = None
                    return
                yield item
        except GeneratorExit:
            # do NOT broadcast here: abandonment fires at GC time, often
            # on the event-loop thread, and a blocking cross-process
            # collective there starves LSP heartbeats (the ProfiledMiner
            # hazard). Leave _open_inner set — the release flag goes out
            # on the executor thread at the next mine()
            # (_spmd_sync_abandoned) or at close().
            inner.close()
            raise

    def close(self) -> None:
        """Leader shutdown: release a mid-chunk follower, then send the
        empty-request stop signal so ``follower_loop`` returns."""
        if self.spmd_leader:
            from tpuminter.parallel import distributed as dist

            self._spmd_sync_abandoned()
            dist.broadcast_bytes(b"")

    def _easy_delegate(self, req: Request) -> Iterator[Optional[Result]]:
        from tpuminter.jax_worker import JaxMiner

        if self._jax_delegate is None:
            self._jax_delegate = JaxMiner()
        yield from self._jax_delegate.mine(req)

    # -- TARGET: pod candidate pipeline ------------------------------------

    def _pod_search(self, lower: int, upper: int,
                    sweep_fn, verify) -> CandidateSearch:
        """Wire one (range, sweep program, verifier) into the shared
        pipelined driver. ``CandidateSearch`` always dispatches full
        ``pod_span`` slabs (its single-compile policy), relying on the
        sweep reporting the LOWEST candidate offset — which the stripe
        design guarantees pod-wide (``parallel.build_candidate_sweep``)."""

        def sweep(base: int, n: int, after):
            # the pod program does not chain: ``after`` is not read.
            # The stripes a call took are not pulled to the host: a
            # device trace shows them, one kernel op a stripe per chip
            found, off, _ = sweep_fn(jnp.uint32(base))
            return pack_handle(found, off)

        return CandidateSearch(
            sweep, resolve_handle, verify, lower, upper,
            slab=self.pod_span, depth=self.depth,
        )

    def _mine_target(self, req: Request) -> Iterator[Optional[Result]]:
        assert req.header is not None and req.target is not None
        template = ops.header_template(req.header)
        if self._sweep_static is None or template != self._template:
            # a new header re-specializes the static sweep (one XLA
            # compile per header — the dynamic-header sweep exists for
            # the rolled path where that would be per-extranonce)
            self._template = template
            self._sweep_static = build_candidate_sweep(
                self.mesh, template,
                slab_per_device=self.stripe_slab,
                n_slabs=self.stripes, tiles_per_step=self.tiles_per_step,
                kernel=self.kernel,
            )
        cap = _biased_cap(req.target)
        header76 = req.header[:76]

        def sweep_fn(base):
            return self._sweep_static(base, cap)

        def verify(nonce: int) -> Tuple[bool, int]:
            h = chain.hash_to_int(
                chain.dsha256(header76 + struct.pack("<I", nonce))
            )
            return h <= req.target, h

        search = self._pod_search(req.lower, req.upper, sweep_fn, verify)
        for _ in search.events():
            yield None
        yield self._fast_result(req, search)

    # -- TARGET + extranonce rolling (pod-scale BASELINE.json:9-10) --------

    def _mine_rolled(self, req: Request) -> Iterator[Optional[Result]]:
        """Pod-scale batched rolled sweep (``tpuminter.rolled``): ONE
        ``CandidateSearch`` over global indices whose windows are
        ``parallel.build_rolled_sweep`` dispatches — device-major
        interleaved roll rows with stripe-synchronous ICI early exit —
        fed by one batched roll call per window (``rolled.job_roll``:
        the template is an operand, one compiled roll per layout). The
        pod stops re-entering host orchestration 2^ext_bits times per
        chunk. A window is planned up to the chunk's ``upper``; rows
        past it stay padding (valid 0), which the kernel skips."""
        assert req.header is not None and req.target is not None
        from tpuminter import rolled
        from tpuminter.parallel import build_rolled_sweep

        rolled._check_roll_batch(self.roll_batch)
        width = rolled.tile_width(req.nonce_bits, self.slab_per_device)
        rows = -(-(self.roll_batch + 2) // self.n_dev) * self.n_dev
        window = (rows - 2) * width
        if window >= 1 << 32:
            raise ValueError(
                "rolled window (rows × width) must stay below 2^32; "
                "shrink roll_batch or slab_per_device"
            )
        key = (width, rows, self.kernel, self._cand_bits)
        if key not in self._rolled_sweeps:
            self._rolled_sweeps[key] = build_rolled_sweep(
                self.mesh, width=width, rows=rows,
                tiles_per_step=self.tiles_per_step, kernel=self.kernel,
                cand_bits=self._cand_bits,
            )
        sweep_prog = self._rolled_sweeps[key]
        roll = rolled.job_roll(req)
        cap = _biased_cap(req.target)
        hard_end = (1 << rolled.span_bits(req)) - 1
        n_dev = self.n_dev

        def sweep(start: int, n: int, after):
            # does not chain: ``after`` is not read
            plan = rolled.plan_tiles(
                start, min(n, req.upper - start + 1), req.nonce_bits,
                width, rows, hard_end, interleave=n_dev,
            )
            mids, tails = roll(plan)
            found, first, _ = sweep_prog(
                mids, tails, jnp.asarray(plan.bases),
                jnp.asarray(plan.valids), jnp.asarray(plan.goffs), cap,
            )
            return pack_handle(found, first)

        search = CandidateSearch(
            sweep, resolve_handle, rolled.rolled_verifier(req),
            req.lower, req.upper, slab=window, depth=self.depth,
            domain=1 << rolled.span_bits(req),
        )
        for _ in search.events():
            rolled.report_search_progress(search, req.lower, self.progress_cb)
            yield None
        yield self._fast_result(req, search)

    def _fast_result(self, req: Request, search: CandidateSearch) -> Result:
        out = search.outcome
        if out.found:
            return Result(
                req.job_id, req.mode, out.nonce, out.hash_value,
                found=True, searched=out.searched, chunk_id=req.chunk_id,
            )
        best = out.best  # exact range min iff any candidate surfaced
        hash_value, nonce = best if best else (MIN_UNTRACKED, req.lower)
        return Result(
            req.job_id, req.mode, nonce, hash_value, found=False,
            searched=out.searched, chunk_id=req.chunk_id,
        )

    # -- TARGET with exact min tracking (--exact-min) ----------------------

    def _resolved_kernel(self) -> str:
        """The ``"auto"`` kernel choice, resolved against the backend."""
        if self.kernel != "auto":
            return self.kernel
        return "jnp" if jax.default_backend() == "cpu" else "pallas"

    @property
    def _exact_bpd(self) -> int:
        """Per-chip batch of the jnp exact-min sweep, capped at 2^16
        (full digests are 32× the candidate kernel's memory per nonce)."""
        return min(self.slab_per_device, 1 << 16)

    @property
    def exact_min_span(self) -> int:
        """Nonces one exact-min device call covers. Exposed so test
        code (and ``_mine_target_exact`` itself) never re-derives
        the formula — the loop stride and the compiled sweep's coverage
        must come from one place or they drift apart silently. Engine-
        dependent: the Pallas sweep folds a whole slab per chip per
        call; the jnp CI engine keeps its small memory-capped batches."""
        if self._resolved_kernel() == "pallas":
            return self.n_dev * self.slab_per_device
        return self.n_dev * self.n_slabs * self._exact_bpd

    def _mine_target_exact(self, req: Request) -> Iterator[Optional[Result]]:
        """TARGET with CpuMiner-compatible exhausted minima: full
        digests on every chip (no candidate shortcut), pod-wide winner
        or-reduce AND an exact lexicographic-min fold. Same engine split
        as MIN: the fused Pallas tracking kernel per chip in production,
        the jnp ``build_target_sweep`` on the CPU mesh (CI)."""
        if self._resolved_kernel() == "pallas":
            yield from self._mine_target_exact_pallas(req)
        else:
            yield from self._mine_target_exact_jnp(req)

    def _mine_target_exact_pallas(
        self, req: Request
    ) -> Iterator[Optional[Result]]:
        """Production pod exact-min (VERDICT r5 weak #1 — the measured
        ~1000× gap): ``pallas_search_target`` per chip under shard_map
        (``parallel.build_exact_sweep_pallas``), slab-scale spans, and
        the host loop double-buffered ``depth`` deep so the dispatch
        overlaps device compute. The early-exit check
        lags the in-flight depth by design — spans resolve in order, so
        a winner in span *i* is reported before span *i+1*'s result is
        ever looked at, and the abandoned in-flight handles are free
        (the ``CandidateSearch`` contract). Ragged tails run the
        single-chip kernel."""
        from tpuminter.kernels import pallas_search_target

        assert req.header is not None and req.target is not None
        template = ops.header_template(req.header)
        tw = tuple(int(t) for t in ops.target_to_words(req.target))
        key = (template, tw)
        if self._exact_pallas is None or key != self._exact_pallas_key:
            self._exact_pallas_key = key
            self._exact_pallas = build_exact_sweep_pallas(
                self.mesh, template, tw,
                slab_per_device=self.slab_per_device,
                tiles_per_step=self.tiles_per_step,
            )
        sweep = self._exact_pallas
        span = self.exact_min_span
        n_full = (req.upper - req.lower + 1) // span
        starts = (req.lower + i * span for i in range(n_full))
        best: Optional[Tuple[int, int]] = None  # (hash, nonce)
        searched = 0
        for start, handle in pipeline_spans(
            starts, lambda s: sweep(jnp.uint32(s)), depth=self.depth
        ):
            row = pull(handle)  # one pull: [found, win, words×8, min]
            if int(row[0]):
                nonce = int(row[1])
                # recompute the winner's hash host-side (one nonce, cheap
                # and self-verifying); coverage counts the winning chip's
                # in-kernel prefix — an honest lower bound, as in the jnp
                # engine's completed-rounds approximation
                h = chain.hash_to_int(chain.dsha256(
                    req.header[:76] + struct.pack("<I", nonce)
                ))
                yield Result(
                    req.job_id, req.mode, nonce, h, found=True,
                    searched=searched + (nonce - start + 1),
                    chunk_id=req.chunk_id,
                )
                return
            cand = (_hash_words_to_int(row[2:10]), int(row[10]))
            if best is None or cand < best:
                best = cand
            searched += span
            yield None
        # ragged tail: single-chip tracking-kernel slabs
        idx = req.lower + n_full * span
        while idx <= req.upper:
            take = min(self.slab_per_device, req.upper - idx + 1)
            found, first, min_words, min_off = pallas_search_target(
                template, tw, jnp.uint32(idx), take, self.tiles_per_step
            )
            if int(found):
                nonce = idx + int(first)
                h = chain.hash_to_int(chain.dsha256(
                    req.header[:76] + struct.pack("<I", nonce)
                ))
                yield Result(
                    req.job_id, req.mode, nonce, h, found=True,
                    searched=searched + int(first) + 1,
                    chunk_id=req.chunk_id,
                )
                return
            cand = (
                _hash_words_to_int(np.asarray(min_words)),
                idx + int(min_off),
            )
            if best is None or cand < best:
                best = cand
            searched += take
            idx += take
            yield None
        yield Result(
            req.job_id, req.mode, best[1], best[0], found=False,
            searched=searched, chunk_id=req.chunk_id,
        )

    def _mine_target_exact_jnp(self, req: Request) -> Iterator[Optional[Result]]:
        """CPU-mesh/CI exact-min engine: the jnp ``build_target_sweep``
        with dynamic limit masking (small batches, ragged spans exact
        on device)."""
        assert req.header is not None and req.target is not None
        template = ops.header_template(req.header)
        bpd = self._exact_bpd
        if self._exact_sweep is None or template != self._exact_template:
            self._exact_template = template
            self._exact_sweep = build_target_sweep(
                self.mesh, template, batch_per_device=bpd,
                n_batches=self.n_slabs,
            )
        span = self.exact_min_span
        target_words = jnp.asarray(ops.target_to_words(req.target))
        limit = jnp.uint32(req.upper)
        best: Optional[Tuple[int, int]] = None  # (hash, nonce)
        searched = 0
        idx = req.lower
        while idx <= req.upper:
            found, nonce, digest, b = self._exact_sweep(
                jnp.uint32(idx), target_words, limit
            )
            covered = min(idx + span - 1, req.upper) - idx + 1
            if int(found):
                # early exit: approximate coverage by completed rounds
                searched += min(int(b) * bpd * self.n_dev, covered)
                h = ops.digest_to_int(np.asarray(digest))
                yield Result(
                    req.job_id, req.mode, int(nonce), h, found=True,
                    searched=searched, chunk_id=req.chunk_id,
                )
                return
            searched += covered
            cand = (ops.digest_to_int(np.asarray(digest)), int(nonce))
            if best is None or cand < best:
                best = cand
            idx += span
            yield None
        yield Result(
            req.job_id, req.mode, best[1], best[0], found=False,
            searched=searched, chunk_id=req.chunk_id,
        )

    # -- MIN (toy) dialect: pod argmin fold --------------------------------

    def _mine_min(self, req: Request) -> Iterator[Optional[Result]]:
        if self._resolved_kernel() == "pallas":
            yield from self._mine_min_pallas(req)
        else:
            yield from self._mine_min_jnp(req)

    def _mine_min_pallas(self, req: Request) -> Iterator[Optional[Result]]:
        """Production pod MIN: the fused Pallas toy kernel per chip
        under shard_map (VERDICT r3 weak #3 — the jnp fold at 2^16
        batches left the pod orders of magnitude below the chip's
        demonstrated single-chip toy rate). Full spans ride the pod
        step, double-buffered ``depth`` deep (VERDICT r5 weak #2: MIN
        has no early exit, so pipelining away the per-span round trip
        is pure win); the ragged tail runs the single-chip kernel."""
        from tpuminter.kernels import pallas_min_toy

        template = ops.toy_template(req.data)
        if self._min_sweep is None or template != self._min_template:
            self._min_template = template
            self._min_sweep = build_min_sweep_pallas(
                self.mesh, template,
                slab_per_device=self.slab_per_device,
                tiles_per_step=self.tiles_per_step,
            )
        span = self.n_dev * self.slab_per_device
        n_full = (req.upper - req.lower + 1) // span
        starts = (req.lower + i * span for i in range(n_full))

        def dispatch(start):
            fh, fl, nh, nl = self._min_sweep(
                jnp.uint32(start >> 32), jnp.uint32(start & 0xFFFFFFFF)
            )
            # one device array per span: four separate scalar pulls
            # would cost four round trips (cf. search.pack_handle)
            return jnp.stack([fh, fl, nh, nl])

        best: Optional[Tuple[int, int]] = None  # (hash, nonce)
        for _, handle in pipeline_spans(starts, dispatch, depth=self.depth):
            row = pull(handle)
            cand = (
                (int(row[0]) << 32) | int(row[1]),
                (int(row[2]) << 32) | int(row[3]),
            )
            if best is None or cand < best:
                best = cand
            yield None
        idx = req.lower + n_full * span
        while idx <= req.upper:  # ragged tail, single-chip slabs
            take = min(self.slab_per_device, req.upper - idx + 1)
            fh, fl, off = pallas_min_toy(
                template, jnp.uint32(idx >> 32), jnp.uint32(idx & 0xFFFFFFFF),
                take, self.tiles_per_step,
            )
            cand = ((int(fh) << 32) | int(fl), idx + int(off))
            if best is None or cand < best:
                best = cand
            idx += take
            yield None
        yield Result(
            req.job_id, req.mode, best[1], best[0], found=True,
            searched=req.upper - req.lower + 1, chunk_id=req.chunk_id,
        )

    def _mine_min_jnp(self, req: Request) -> Iterator[Optional[Result]]:
        """CPU-mesh/CI MIN path: jnp fold with dynamic limit masking."""
        template = ops.toy_template(req.data)
        batch_per_device = min(self.slab_per_device, 1 << 16)
        if self._fold is None or template != self._fold_template:
            self._fold_template = template
            self._fold = build_min_fold(
                self.mesh, template, batch_per_device=batch_per_device
            )
        fold = self._fold
        span = self.n_dev * batch_per_device
        lim_hi = jnp.uint32(req.upper >> 32)
        lim_lo = jnp.uint32(req.upper & 0xFFFFFFFF)
        best: Optional[Tuple[int, int]] = None  # (hash, nonce)
        idx = req.lower
        while idx <= req.upper:
            # nonces past `upper` in the final ragged span are masked
            # out of the fold on device (build_min_fold's limit args)
            fh, fl, nh, nl = fold(
                jnp.uint32(idx >> 32), jnp.uint32(idx & 0xFFFFFFFF),
                lim_hi, lim_lo,
            )
            cand = (
                (int(fh) << 32) | int(fl),
                (int(nh) << 32) | int(nl),
            )
            if best is None or cand < best:
                best = cand
            idx += span
            yield None
        yield Result(
            req.job_id, req.mode, best[1], best[0], found=True,
            searched=req.upper - req.lower + 1, chunk_id=req.chunk_id,
        )

    # -- SCRYPT: pod data-parallel sweep -----------------------------------

    def _mine_scrypt(self, req: Request) -> Iterator[Optional[Result]]:
        """Memory-hard dialect sharded over the mesh: each chip hashes a
        contiguous batch through the jnp scrypt pipeline and the winner/
        min folds ride ICI (``parallel.build_scrypt_sweep``). Full spans
        are double-buffered ``depth`` deep (VERDICT r5 weak #2: the
        per-span sync was the measured ~18% pod-vs-single-chip scrypt
        gap); the early-exit check lags the in-flight depth, which is
        sound because spans resolve in order. Rolled jobs reuse the
        host-rolled segment iterator (one roll per 2^nonce_bits hashes
        is noise at scrypt rates)."""
        from tpuminter.jax_worker import JaxMiner
        from tpuminter.ops import scrypt as scrypt_ops
        from tpuminter.parallel import build_scrypt_sweep

        assert req.target is not None
        bpd = self.scrypt_batch or (
            16384 if jax.default_backend() != "cpu" else 64
        )
        if self._scrypt_sweep is None:
            self._scrypt_sweep = build_scrypt_sweep(
                self.mesh, batch_per_device=bpd
            )
        step = self._scrypt_sweep
        span = self.n_dev * bpd
        target_words = jnp.asarray(ops.target_to_words(req.target))
        delegate = JaxMiner(scrypt_batch=bpd)
        best: Optional[Tuple[int, int]] = None  # (hash, global index)
        searched = 0
        for hdr76, base_g, lo, hi in delegate._scrypt_segments(req):
            hw19 = jnp.asarray(scrypt_ops.header_to_words(hdr76))
            n_full = (hi - lo + 1) // span
            starts = (lo + i * span for i in range(n_full))

            def dispatch(nonce, _hw=hw19):
                found, win_nonce, win_digest, min_digest, min_nonce = step(
                    _hw, jnp.uint32(nonce), target_words
                )
                # one device array per span (cf. search.pack_handle):
                # [found, win_nonce, min_nonce, win_digest×8, min_digest×8]
                return jnp.concatenate([
                    jnp.stack([found, win_nonce, min_nonce]),
                    win_digest, min_digest,
                ])

            for nonce, handle in pipeline_spans(
                starts, dispatch, depth=self.depth
            ):
                row = pull(handle)
                if int(row[0]):
                    g = base_g | int(row[1])
                    h = ops.digest_to_int(row[3:11])
                    yield Result(
                        req.job_id, req.mode, g, h, found=True,
                        searched=searched + (int(row[1]) - nonce + 1),
                        chunk_id=req.chunk_id,
                    )
                    return
                cand = (ops.digest_to_int(row[11:19]), base_g | int(row[2]))
                if best is None or cand < best:
                    best = cand
                searched += span
                yield None
            tail_lo = lo + n_full * span
            if tail_lo <= hi:
                # ragged tail: the pod step has a fixed span, so the
                # remainder runs through the single-chip path (same
                # pipeline, smaller batch shape)
                sub = Request(
                    job_id=req.job_id, mode=req.mode, lower=tail_lo,
                    upper=hi, header=hdr76 + bytes(4),
                    target=req.target, chunk_id=req.chunk_id,
                )
                tail_result: Optional[Result] = None
                for item in delegate._mine_scrypt(sub):
                    if item is None:
                        yield None
                    else:
                        tail_result = item
                assert tail_result is not None
                searched += tail_result.searched
                if tail_result.found:
                    yield Result(
                        req.job_id, req.mode, base_g | tail_result.nonce,
                        tail_result.hash_value, found=True,
                        searched=searched, chunk_id=req.chunk_id,
                    )
                    return
                cand = (tail_result.hash_value, base_g | tail_result.nonce)
                if best is None or cand < best:
                    best = cand
        yield Result(
            req.job_id, req.mode, best[1], best[0],
            found=best[0] <= req.target,
            searched=searched, chunk_id=req.chunk_id,
        )
