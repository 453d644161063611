"""Pipelined candidate search: the production TARGET-mode driver.

The fast kernel (``kernels.pallas_search_candidates``) returns only a
*candidate* — the first nonce in a swept range whose double-SHA digest
word 7 is zero (top 32 hash bits zero). That design moves everything
rare off the device: full-hash evaluation, the target compare, and the
decision to keep searching all happen host-side, once per ~2^32 hashes.
This module owns the host half:

- **Pipelining.** Device calls are issued ``depth`` deep before the
  first result is read, so the per-call host dispatch latency
  overlaps device compute.
  Measured on v5e: 0.73 GH/s synchronous → ≥1.0 GH/s pipelined.
- **Verification.** A candidate is verified host-side against the real
  target (``chain.dsha256``); the kernel's necessary-condition test has
  a ~1-per-2^32 false-positive rate at real difficulties.
- **Remainder re-issue.** A call that reports a candidate early-exited:
  offsets past the candidate are unsearched. On a false positive the
  remainder range is pushed to the *front* of the work queue.
- **Ordered acceptance.** A verified win W is only accepted once every
  nonce below W has been searched, so the reported winner is exactly
  the lowest winning nonce in the range — the same contract as the
  sequential CPU miner (SURVEY.md §3.2's loop semantics).
- **Chained skip.** Each dispatch is handed the handle of the sweep in
  flight just before it. A sweep that chains on it (the single-chip
  kernel's ``stop`` operand) does no work when that handle reports a
  candidate or a skip, so the slab queued behind a winner costs the
  device microseconds, not a slab. A skipped range goes back to the
  work queue; after a candidate resolves, no new dispatch chains on a
  handle issued before it, so a false positive skips at most ``depth``
  sweeps and never cascades.

The driver is deliberately generic over three callables (``sweep``,
``resolve``, ``verify``) so its queueing/ordering logic is testable on
CPU with a scripted fake device (tests/test_search.py) and reusable by
the single-chip TpuMiner, the rolled sweeps and the pod.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator, List, Optional, Tuple

from tpuminter.spans import DISPATCH, RESOLVE, span

__all__ = [
    "CandidateSearch", "SearchOutcome", "pipeline_spans", "pull", "timed_call",
]

#: sweep(base, n, after) -> opaque handle (asynchronous dispatch);
#: ``after`` is the handle of the sweep in flight just before, or None
SweepFn = Callable[[int, int, Optional[object]], object]
#: resolve(handle) -> (found, first_off, skipped); blocks until the call
#: is done. ``skipped`` is nonzero only for a sweep that chained on
#: ``after`` and did no work.
ResolveFn = Callable[[object], Tuple[int, int, int]]
#: verify(nonce) -> (wins, hash_value) — full host-side evaluation
VerifyFn = Callable[[int], Tuple[bool, int]]


def pack_handle(found, off, skipped=None):
    """Pack a sweep's (found, first_off[, skipped]) device scalars into
    ONE device array — the canonical CandidateSearch handle. Resolving
    the scalars separately costs a host round-trip each per slab (the
    measured 0.98 → 1.005 GH/s difference). Layout: index 0 = found,
    1 = first_off, 2 = skipped, present only for a sweep that chains
    (``kernels.pallas_search_candidates``' ``stop``) — keep in sync with
    :func:`resolve_handle`, the only host reader, and with that
    kernel's ``stop`` test, the device reader."""
    import jax.numpy as jnp

    return jnp.stack([found, off] if skipped is None else [found, off, skipped])


def pull(handle):
    """Block on one device call's result and copy it to the host: the
    sync point of every pipelined loop, recorded as a resolve span."""
    import numpy as np

    with span(RESOLVE):
        return np.asarray(handle)


def resolve_handle(handle) -> Tuple[int, int, int]:
    """Blocking single-pull resolve of a :func:`pack_handle` handle:
    ``(found, first_off, skipped)``, skipped 0 for a handle without it."""
    import numpy as np

    arr = np.asarray(handle)
    return int(arr[0]), int(arr[1]), int(arr[2]) if arr.size > 2 else 0


def timed_call(fn, args) -> float:
    """Wall-clock ONE device call, dispatch through completion — the
    probe primitive behind the one-shot width autotune
    (``ops.splitmix.autotune_lane_width``).
    Blocks via ``block_until_ready`` when the return value offers it;
    callers that sync some other way (``np.asarray`` inside ``fn``)
    just return a plain value."""
    import time

    t0 = time.perf_counter()
    out = fn(*args)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return time.perf_counter() - t0


def pipeline_spans(
    spans: Iterable, dispatch: Callable[..., object], depth: int = 2
) -> Iterator[Tuple[object, object]]:
    """Double-buffer a host loop over device calls: the generic form of
    the ``CandidateSearch`` depth-``k`` in-flight trick, for dialects
    with no early-exit bookkeeping to manage (MIN, scrypt, exact-min).

    Yields ``(span, handle)`` pairs in dispatch order with up to
    ``depth`` dispatches outstanding when the caller blocks on a
    handle — so the per-call host dispatch latency
    overlaps device compute instead of serializing with it (the same
    0.73 → ≥1.0 GH/s step PERF.md records for the TARGET pipeline).
    ``dispatch(span)`` must be non-blocking (JAX async dispatch is);
    the caller resolves each yielded handle (:func:`pull`), which is the
    only sync point. Each dispatch is recorded as a dispatch span.

    Early exit: a caller that stops consuming (found a winner,
    Cancel abandoned the generator) simply leaves the in-flight
    handles unresolved — free for JAX async arrays (same contract as
    ``CandidateSearch``'s abandoned handles). Cancel latency therefore
    stays bounded by ONE span resolution: the role loop's yield points
    sit between resolved spans, exactly as in the synchronous loop.
    """
    if depth < 1:
        raise ValueError("depth must be >= 1")
    inflight: deque = deque()
    for item in spans:
        with span(DISPATCH):
            handle = dispatch(item)
        inflight.append((item, handle))
        if len(inflight) >= depth:
            yield inflight.popleft()
    while inflight:
        yield inflight.popleft()


@dataclass
class SearchOutcome:
    """Terminal state of a :class:`CandidateSearch` run."""

    found: bool
    nonce: Optional[int] = None
    hash_value: Optional[int] = None
    searched: int = 0
    #: every candidate surfaced (nonce, hash) — at exhaustion their min
    #: is the exact range minimum *iff* any candidate existed
    candidates: List[Tuple[int, int]] = field(default_factory=list)

    @property
    def best(self) -> Optional[Tuple[int, int]]:
        """(hash, nonce) minimum over surfaced candidates, or None."""
        if not self.candidates:
            return None
        return min((h, n) for n, h in self.candidates)


class CandidateSearch:
    """Exact lowest-winner search over ``[lower, upper]`` (inclusive).

    ``slab`` nonces per device call, ``depth`` calls in flight. Drive it
    with :meth:`events` — a generator yielding ``None`` after every
    resolved call (a natural heartbeat/Cancel point for the worker
    loop); when it stops, :attr:`outcome` is set.

    The index domain defaults to the 32-bit header nonce space;
    ``domain`` widens it for searches over *global* indices — a rolled
    job's (extranonce × nonce) product space (``chain.split_global``),
    where one search instance now spans every extranonce segment and a
    ``sweep`` is a batched multi-roll dispatch (``tpuminter.rolled``).
    Nothing else changes: min-fold/candidate bookkeeping is keyed by the
    same integers ``sweep``/``verify`` speak, whatever they index.

    Contract note (ADVICE.md r2): when a verified win ends the search,
    up to ``depth - 1`` in-flight sweep handles above the winner are
    simply **abandoned, never resolved** (a chaining sweep has skipped
    them on the device). That is free for JAX async
    arrays (the device work is already dispatched and the result is
    garbage-collected), but a ``resolve`` callable that owns real
    resources per handle must tolerate dropped handles — clean them up
    in a finalizer, not in ``resolve``.
    """

    def __init__(
        self,
        sweep: SweepFn,
        resolve: ResolveFn,
        verify: VerifyFn,
        lower: int,
        upper: int,
        *,
        slab: int = 1 << 27,
        depth: int = 2,
        domain: int = 1 << 32,
    ):
        if not 0 <= lower <= upper < domain:
            raise ValueError(f"bad range [{lower}, {upper}] for domain {domain}")
        # 2^32 admits a whole-pod span (PodMiner); the single-chip
        # kernels cap their own n at 2^30 (int32 offset domain)
        if not 1 <= slab <= max(domain, 1 << 32):
            raise ValueError("slab out of range")
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._sweep, self._resolve, self._verify = sweep, resolve, verify
        self.lower, self.upper = lower, upper
        self.slab, self.depth = slab, depth
        # disjoint unsearched ranges, ascending: a re-queued early-exit
        # remainder goes to the FRONT (it is lower than anything else
        # still queued), a skipped range to its place in nonce order
        self._pending: deque = deque([(lower, upper)])
        # (start, end, handle, chainable) FIFO; a sweep chains only on a
        # chainable handle: one issued after the last resolved candidate
        self._inflight: deque = deque()
        self._wins: List[Tuple[int, int]] = []  # (nonce, hash)
        self.outcome: Optional[SearchOutcome] = None
        self._searched = 0
        self._candidates: List[Tuple[int, int]] = []

    @property
    def searched(self) -> int:
        """Nonces verifiably swept so far (early exits count only their
        covered prefix) — the honest throughput numerator."""
        return self._searched

    # -- internals --------------------------------------------------------

    def _issue_one(self) -> None:
        start, end = self._pending.popleft()
        take = min(self.slab, end - start + 1)
        if start + take - 1 < end:
            self._pending.appendleft((start + take, end))
        # ALWAYS dispatch a full slab, even when the logical range is
        # shorter (trailing chunk, post-candidate remainder): the kernel
        # specializes on n at compile time, so a single canonical n means
        # a single compile for the whole mining session — a fresh slab
        # size mid-run costs ~20 s of compile. Sound
        # because the kernel reports the LOWEST candidate offset: a hit
        # past ``end`` (or past 2^32 wrap) proves [start, end] clean.
        after = None
        if self._inflight and self._inflight[-1][3]:
            after = self._inflight[-1][2]
        with span(DISPATCH):
            handle = self._sweep(start, self.slab, after)
        self._inflight.append((start, start + take - 1, handle, True))

    def _unchain_inflight(self) -> None:
        """A candidate resolved: every sweep still in flight may skip on
        it, directly or through a skipped one before it, so no new
        dispatch may chain on them."""
        self._inflight = deque(
            (s, e, h, False) for s, e, h, _ in self._inflight
        )

    def _requeue(self, start: int, end: int) -> None:
        """Put a skipped range back into ``_pending`` in nonce order."""
        at = next(
            (i for i, (s, _) in enumerate(self._pending) if s > start),
            len(self._pending),
        )
        self._pending.insert(at, (start, end))

    def _unsearched_min(self) -> Optional[int]:
        starts = [s for s, _ in self._pending]
        starts += [s for s, _, _, _ in self._inflight]
        return min(starts) if starts else None

    def settled_high_water(self) -> Optional[int]:
        """Highest index ``g`` such that every index in ``[lower, g]``
        has been verifiably swept with no winner accepted below it, or
        None when nothing is settled yet. The source a rolled worker's
        progress beacon reads from: while the search is running, every
        candidate below the unsearched minimum has already been
        host-verified (a win would have finished or pinned the search),
        so ``[lower, settled_high_water()]`` is safe for the coordinator
        to journal as a partial settle."""
        lo = self._unsearched_min()
        if lo is None:
            return self.upper
        if lo <= self.lower:
            return None
        return lo - 1

    def best_candidate(self) -> Optional[Tuple[int, int]]:
        """(hash, nonce) minimum over candidates surfaced so far, or
        None — the running min-fold a progress beacon carries."""
        if not self._candidates:
            return None
        return min((h, n) for n, h in self._candidates)

    def _try_finish(self) -> bool:
        if not self._wins:
            if self._pending or self._inflight:
                return False
            self.outcome = SearchOutcome(
                found=False, searched=self._searched,
                candidates=self._candidates,
            )
            return True
        w_nonce, w_hash = min(self._wins)
        lo = self._unsearched_min()
        if lo is not None and lo < w_nonce:
            return False
        self.outcome = SearchOutcome(
            found=True, nonce=w_nonce, hash_value=w_hash,
            searched=self._searched, candidates=self._candidates,
        )
        return True

    def _prune_pending_above(self, nonce: int) -> None:
        """Ranges entirely above a verified win can never beat it."""
        self._pending = deque(
            (s, e) for s, e in self._pending if s < nonce
        )

    # -- driver -----------------------------------------------------------

    def events(self) -> Iterator[None]:
        """Run to completion; yields after each resolved device call."""
        while True:
            while len(self._inflight) < self.depth and self._pending:
                self._issue_one()
            if not self._inflight:
                assert self._try_finish(), "no work left but not finished"
                return
            start, end, handle, _ = self._inflight.popleft()
            with span(RESOLVE):
                found, off, skipped = self._resolve(handle)
            n = end - start + 1
            if found:
                self._unchain_inflight()
            if skipped:
                # chained behind a candidate: nothing swept
                self._requeue(start, end)
                if self._wins:
                    self._prune_pending_above(min(self._wins)[0])
            elif not found or off >= n:
                # clean sweep: no candidate at any offset within the
                # logical range (a hit past it — oversweep slack or a pad
                # lane — still proves every lower offset candidate-free)
                self._searched += n
            else:
                cand = start + off
                self._searched += off + 1
                if cand < end:
                    # early exit skipped the rest: search it before
                    # anything later (front of queue keeps nonce order)
                    self._pending.appendleft((cand + 1, end))
                wins, hash_value = self._verify(cand)
                self._candidates.append((cand, hash_value))
                if wins:
                    self._wins.append((cand, hash_value))
                    self._prune_pending_above(cand)
            if self._try_finish():
                yield
                return
            yield
