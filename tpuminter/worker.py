"""Worker role: the Miner interface and the CPU reference miner.

Capability-equivalent rebuild of the reference's ``bitcoin/miner/miner.go``
(SURVEY.md §2 #9, §3.2; mount empty per §0): connect, ``Join``, then loop
{ read Request → search the nonce range → write Result }, exiting when the
coordinator connection is declared lost.

Two deliberate departures from the reference shape, both demanded by the
north-star (BASELINE.json:5 "a new TPUMiner satisfies the existing
Miner/Worker interface"):

- **The Miner interface is a cooperative generator,** not a blocking
  call: ``mine(request)`` yields ``None`` between batches and finally a
  ``Result``. The async role loop interleaves those yields with the LSP
  event loop, so heartbeats keep flowing while mining (the reference gets
  this from goroutines; asyncio needs explicit yield points) — and a
  ``Cancel`` for the active job can interrupt mid-range. Device-backed
  miners use the same seam to overlap host control with device compute.
- **Two PoW dialects** (``protocol.PowMode``): the reference's min-hash
  search, and real ``double-SHA256(header ‖ nonce) <= target``.
"""

from __future__ import annotations

import asyncio
import logging
import random
import struct
import time
from collections import deque
from typing import Callable, Iterator, Optional

from tpuminter import chain
from tpuminter import workloads
from tpuminter.coordinator import Coordinator
from tpuminter.lsp import LspClient, LspConnectError, LspConnectionLost, Params
from tpuminter.lsp.params import jittered_backoff
from tpuminter.lsp.params import FAST
from dataclasses import replace as dc_replace

from tpuminter.protocol import (
    MIN_UNTRACKED,
    Assign,
    Beacon,
    Cancel,
    Join,
    Message,
    PowMode,
    ProtocolError,
    Refuse,
    Request,
    Result,
    RollAssign,
    Setup,
    decode_msg,
    encode_msg,
    payload_is_binary,
)

__all__ = [
    "Miner", "CpuMiner", "ProfiledMiner", "run_miner",
    "run_miner_reconnect", "main",
]

log = logging.getLogger("tpuminter.worker")


class Miner:
    """The Worker interface every backend satisfies (BASELINE.json:5).

    Subclasses set ``backend``/``lanes`` (advertised in ``Join``) and
    implement :meth:`mine` as a generator: yield ``None`` whenever it is
    safe to pause (a batch boundary), then yield the chunk's ``Result``
    exactly once and return. The caller may simply abandon the generator
    (on Cancel), so resources must not depend on exhaustion.
    """

    backend = "abstract"
    lanes = 1
    #: internal pipeline-stage size in nonces (Join.span): device miners
    #: that keep several slabs in flight set this so the coordinator
    #: carves chunks covering multiple spans (single-span chunks drain
    #: the pipeline at every boundary — coordinator.SPANS_PER_DISPATCH)
    span = 0
    #: optional ``(high_water, best_nonce, best_hash)`` callback
    #: (``rolled.ProgressFn``) the role loop installs per roll-budget
    #: chunk; rolled mine paths call it at batch/window boundaries with
    #: the settled global-index high-water so the loop can emit Beacon
    #: progress. Runs on the mining (executor) thread — implementations
    #: must stay tiny and lock-free (the installed one just stores a
    #: tuple). None (the default) disables progress tracking entirely.
    progress_cb: Optional[Callable[[int, int, int], None]] = None

    def mine(self, request: Request) -> Iterator[Optional[Result]]:
        raise NotImplementedError

    def compute(self, request: Request) -> Iterator[Optional[Result]]:
        """A registered workload's chunk (ISSUE 15), same generator
        discipline as :meth:`mine`; the engine resolves off
        :attr:`backend`."""
        return workloads.compute(request, engine=self.backend)

    def cancel(self) -> None:
        """Stop the running chunk at its next yield point. Called from
        the role loop's thread while a step of the chunk may be running
        on another; the generator is still closed once that step
        returns. A no-op here: an in-process miner's step cannot be
        interrupted, so closing the generator is the earliest stop."""


class CpuMiner(Miner):
    """hashlib-backed reference miner (≙ the reference's Go hot loop).

    The baseline every accelerated backend is measured against
    (SURVEY.md §6). ``batch`` bounds work between yield points.
    """

    backend = "cpu"

    def __init__(self, batch: int = 4096):
        self.batch = batch

    def mine(self, request: Request) -> Iterator[Optional[Result]]:
        if request.mode == PowMode.MIN:
            yield from self._mine_min(request)
        elif request.rolled:
            yield from self._mine_rolled(request)
        else:
            yield from self._mine_target(request)

    @staticmethod
    def _pow_fn(mode: PowMode):
        """The targeted dialects differ only in the PoW hash
        (protocol.PowMode): double-SHA for TARGET, RFC 7914 scrypt for
        SCRYPT (BASELINE.json:11)."""
        return chain.scrypt_hash if mode == PowMode.SCRYPT else chain.dsha256

    def _mine_min(self, req: Request) -> Iterator[Optional[Result]]:
        best_hash, best_nonce = None, req.lower
        nonce = req.lower
        while nonce <= req.upper:
            stop = min(nonce + self.batch, req.upper + 1)
            for n in range(nonce, stop):
                h = chain.toy_hash(req.data, n)
                if best_hash is None or h < best_hash:
                    best_hash, best_nonce = h, n
            nonce = stop
            if nonce <= req.upper:
                yield None
        yield Result(
            req.job_id, req.mode, best_nonce, best_hash, found=True,
            searched=req.upper - req.lower + 1, chunk_id=req.chunk_id,
        )

    def _mine_target(self, req: Request) -> Iterator[Optional[Result]]:
        assert req.header is not None and req.target is not None
        powf = self._pow_fn(req.mode)
        prefix = req.header[:76]
        best_hash, best_nonce = None, req.lower
        nonce = req.lower
        while nonce <= req.upper:
            stop = min(nonce + self.batch, req.upper + 1)
            for n in range(nonce, stop):
                h = chain.hash_to_int(powf(prefix + struct.pack("<I", n)))
                if best_hash is None or h < best_hash:
                    best_hash, best_nonce = h, n
                    if h <= req.target:  # early exit: a winner ends the chunk
                        yield Result(
                            req.job_id, req.mode, n, h, found=True,
                            searched=n - req.lower + 1, chunk_id=req.chunk_id,
                        )
                        return
            nonce = stop
            if nonce <= req.upper:
                yield None
        yield Result(
            req.job_id, req.mode, best_nonce, best_hash,
            found=best_hash <= req.target,
            searched=req.upper - req.lower + 1, chunk_id=req.chunk_id,
        )

    def _mine_rolled(self, req: Request) -> Iterator[Optional[Result]]:
        """Extranonce-rolling TARGET search over global indices
        (``chain.split_global``): host reference semantics — the header
        is re-rolled whenever the index crosses an extranonce boundary.
        The ground truth the device backends are pinned against.
        """
        assert req.target is not None
        powf = self._pow_fn(req.mode)
        cb = chain.CoinbaseTemplate(
            req.coinbase_prefix, req.coinbase_suffix, req.extranonce_size
        )
        best_hash, best_nonce = None, req.lower
        for en, base_g, n_lo, n_hi in chain.rolled_segments(
            req.lower, req.upper, req.nonce_bits
        ):
            prefix = chain.rolled_header(req.header, cb, req.branch, en).pack()[:76]
            nonce = n_lo
            while nonce <= n_hi:
                stop = min(nonce + self.batch, n_hi + 1)
                for n in range(nonce, stop):
                    h = chain.hash_to_int(powf(prefix + struct.pack("<I", n)))
                    if best_hash is None or h < best_hash:
                        g = base_g | n
                        best_hash, best_nonce = h, g
                        if h <= req.target:
                            yield Result(
                                req.job_id, req.mode, g, h, found=True,
                                searched=g - req.lower + 1, chunk_id=req.chunk_id,
                            )
                            return
                nonce = stop
                # + not |: at a segment end nonce is n_hi+1, past the mask
                if base_g + nonce <= req.upper:
                    if self.progress_cb is not None:
                        # every index through base_g + nonce - 1 is fully
                        # hashed with no winner (a winner returned above)
                        self.progress_cb(
                            base_g + nonce - 1, best_nonce,
                            best_hash if best_hash is not None
                            else MIN_UNTRACKED,
                        )
                    yield None
        yield Result(
            req.job_id, req.mode, best_nonce, best_hash,
            found=best_hash <= req.target,
            searched=req.upper - req.lower + 1, chunk_id=req.chunk_id,
        )


class ProfiledMiner(Miner):
    """Decorator Miner: records one ``jax.profiler`` trace of a WARM
    steady-state window — the work between generator steps 1 and 3 of
    the first sufficiently long chunk — to ``log_dir`` (SURVEY.md §5
    observability, the device-side complement to the coordinator's
    per-worker rates).

    Why a window and not the whole chunk: tracing from the first step
    swallows the initial XLA compile (tens of seconds), and the
    profiler's stop/serialize of such a trace blocks
    the interpreter long enough that LSP epoch heartbeats stop and the
    coordinator declares the worker dead mid-profile (observed live).
    A two-step warm window captures the steady-state kernel pipeline —
    the thing worth looking at — and serializes in milliseconds. The
    window opens at step 1: a device miner's first yield happens only
    after its first batch RESOLVES, so the compile is already behind
    it. Short chunks that end inside the window still close the trace
    cleanly (the ``finally``), capturing whatever ran.
    """

    _START_STEP, _STOP_STEP = 1, 3

    def __init__(self, inner: Miner, log_dir: str):
        self._inner = inner
        self._log_dir = log_dir
        self._traced = False
        self._tracing = False
        self.backend = inner.backend
        self.lanes = inner.lanes
        self.span = inner.span

    def _stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()
        self._tracing = False

    def mine(self, request: Request) -> Iterator[Optional[Result]]:
        # The role loop may ABANDON a mid-trace generator on Cancel (the
        # Miner contract allows it), so closing the trace must not
        # depend on this generator finishing — and a GC-time finalizer
        # would run jax's trace serialization on the event-loop thread,
        # the heartbeat-starving hazard the class docstring describes.
        # Instead any still-open trace is closed HERE, at the start of
        # the next chunk: generator bodies run on the executor thread.
        if self._tracing:
            log.info("closing trace abandoned by a cancelled chunk")
            self._stop_trace()
        # the role loop (re)installs progress_cb on THIS wrapper per
        # chunk; the inner miner is what actually reads it while mining
        self._inner.progress_cb = self.progress_cb
        if self._traced:
            yield from self._inner.mine(request)
            return
        import jax

        step = 0
        try:
            for item in self._inner.mine(request):
                step += 1
                if step == self._START_STEP and not self._traced:
                    log.info(
                        "profiling steady-state window to %s", self._log_dir
                    )
                    jax.profiler.start_trace(self._log_dir)
                    self._tracing = True
                    self._traced = True
                elif step == self._STOP_STEP and self._tracing:
                    self._stop_trace()
                yield item
        except BaseException:
            # exceptions propagate on the executor thread — safe (and
            # necessary) to serialize the trace here before re-raising
            if self._tracing:
                self._stop_trace()
            raise
        if self._tracing:  # chunk ended inside the window
            self._stop_trace()

    def close(self) -> None:
        """Flush a still-open trace at worker shutdown (``run_miner``'s
        finally): heartbeats no longer matter then, so serializing on
        the caller's thread is fine. Covers the Cancel-then-exit path
        where no further ``mine()`` call would ever close it. Delegates
        to the wrapped miner's own close (a multi-host PodMiner must
        still release its followers)."""
        if self._tracing:
            log.info("flushing open trace at shutdown")
            self._stop_trace()
        closer = getattr(self._inner, "close", None)
        if callable(closer):
            closer()


async def run_miner(
    host: str,
    port: int,
    miner: Miner,
    *,
    params: Optional[Params] = None,
    on_result: Optional[Callable[[Result], None]] = None,
    binary: bool = True,
    connect_epochs: Optional[int] = None,
    roll: bool = True,
    beacon_interval: float = 2.0,
    clock: Optional[Callable[[], float]] = None,
) -> None:
    """Worker role main loop; returns when the coordinator is lost.

    ``clock`` (ISSUE 20) is this worker's monotonic-clock seam —
    everything time-based on this side (beacon pacing here, redial
    backoff in :func:`run_miner_reconnect`) reads it, so a chaos cell
    can install a :class:`tpuminter.chaos.ClockSkewPlan` fork and lie
    to the worker *differently* than to the coordinator. Skew on this
    seam may only ever degrade to delays (late beacons, a stretched or
    hastened redial) — never to wrong results, because no correctness
    decision on the worker reads the clock.

    ≙ reference ``miner.go`` ``main`` (SURVEY.md §3.2), with Cancel
    handling layered in: while a generator step runs, the loop waits on
    the step and an LSP read together, so every message is handled as it
    arrives. A ``Cancel`` for the chunk being mined calls
    :meth:`Miner.cancel` at once (a :class:`~tpuminter.miner_proc.
    ProcessMiner` forwards it to its child, which stops at its next
    yield point), and the generator is closed when the running step
    returns; any other message read mid-mine is queued in arrival order
    and handled after.

    A job this worker has just answered is not mined further. The
    coordinator finishes a targeted job on any accepted found Result
    (``Coordinator._accept_result``, and ``_settle_audit`` for an audit
    that mines a winner) and retires it at once, so the next message it
    sends this worker is ``Cancel(job)``, which covers every chunk of
    the job the worker still holds. So once the loop writes a found
    TARGET or SCRYPT Result that passes the coordinator's own check
    (``Coordinator._verify_result``), it HOLDS every Assign or
    RollAssign of that job it reads next (the pipelined chunk queued
    behind the winner's) instead of mining it. The job's Cancel drops
    the held chunks, with no Result and no Refuse: the coordinator has
    already booked them cancelled. Any other message first releases
    them, to be mined in arrival order as if never held, so a Cancel
    that never comes costs time, not coverage; a Result that fails the
    check holds nothing.

    ``binary`` advertises the struct-packed codec in the Join
    (``protocol`` module docstring): Results/Refuses switch to binary
    only after the coordinator has SENT us a binary payload — proof it
    decodes them — so an old coordinator gets JSON forever and nothing
    needs a flag day. ``binary=False`` pins this worker to JSON (the
    interop tests' "old peer" stand-in).

    ``roll`` advertises the roll-budget dialect the same way: a
    roll-capable coordinator may then dispatch this worker
    :class:`RollAssign` chunks (extranonce-unit, ``count · 2^nonce_bits``
    indices each), and for exactly those chunks the loop emits
    :class:`Beacon` progress — the settled global-index high-water plus
    the running min-fold — at most every ``beacon_interval`` seconds
    (the cadence knob; ≤ 0 disables emission). Beacons only flow for
    chunks that ARRIVED as a RollAssign, so an old coordinator never
    sees one. ``roll=False`` pins this worker to classic global-index
    chunks (the interop tests' "old peer" stand-in).
    """
    mono = clock if clock is not None else time.monotonic
    client = await LspClient.connect(
        host, port, params or FAST, connect_epochs=connect_epochs
    )
    client.write(encode_msg(Join(
        backend=miner.backend, lanes=miner.lanes, span=miner.span,
        codec="bin" if binary else "json", roll=roll,
        # advertise every registered workload (ISSUE 15): the
        # coordinator only dispatches a workload job to workers that
        # named it here — an old worker advertises nothing and keeps
        # getting mining chunks, no flag day
        workloads=workloads.names(),
    )))
    speak_binary = False

    def note_codec(raw) -> None:
        # negotiation hook: one binary payload from the coordinator
        # flips our send side (never flips back — the peer's codec
        # choice is per-incarnation)
        nonlocal speak_binary
        if binary and not speak_binary and payload_is_binary(raw):
            speak_binary = True

    pending: "deque[Message]" = deque()
    #: the job this worker's own found, checked Result answered, and
    #: the Assigns/RollAssigns of it read since (docstring: the hold)
    answered: Optional[int] = None
    held: list = []
    dropped = 0
    read_task: Optional[asyncio.Task] = None
    #: job_id → template Request from a Setup (insertion-ordered so the
    #: cap evicts oldest-first; Cancel evicts eagerly, the cap only mops
    #: up after jobs that finished without one reaching this worker). If
    #: eviction ever races a live job, the Refuse seam below heals it.
    templates: dict = {}
    _TEMPLATE_CAP = 256
    try:
        while True:
            # -- next message: drained backlog first, then the wire ------
            if pending:
                msg = pending.popleft()
            else:
                if read_task is None:
                    read_task = asyncio.ensure_future(client.read())
                raw = await read_task
                read_task = None
                note_codec(raw)
                msg = _safe_decode(raw)
                if msg is None:
                    continue
            if answered is not None:
                if (
                    isinstance(msg, (Assign, RollAssign))
                    and msg.job_id == answered
                ):
                    held.append(msg)
                    continue
                job_id, released = answered, held
                answered, held = None, []
                if isinstance(msg, Cancel) and msg.job_id == job_id:
                    # the coordinator booked these chunks cancelled when
                    # it wrote this Cancel: no Result, no Refuse
                    for h in released:
                        log.info(
                            "worker: job %d answered; chunk %d dropped "
                            "unmined", h.job_id, h.chunk_id,
                        )
                    dropped += len(released)
                elif released:
                    # backstop: mine the held chunks in arrival order,
                    # as if never held, then handle this message
                    pending.appendleft(msg)
                    pending.extendleft(reversed(released))
                    continue
            if isinstance(msg, Cancel):
                templates.pop(msg.job_id, None)
                continue  # for a job we are not mining: stale, drop
            if isinstance(msg, Setup):
                templates[msg.request.job_id] = msg.request
                while len(templates) > _TEMPLATE_CAP:
                    templates.pop(next(iter(templates)))
                continue
            roll_chunk = False
            if isinstance(msg, (Assign, RollAssign)):
                tmpl = templates.get(msg.job_id)
                if tmpl is None:
                    # template missing (evicted by a hedge-loser Cancel or
                    # the cap): tell the coordinator so it requeues the
                    # chunk and re-ships the Setup — silently dropping
                    # would leave us marked busy-forever on its books
                    log.warning(
                        "worker: no template for job %d; refusing chunk %d",
                        msg.job_id, msg.chunk_id,
                    )
                    client.write(encode_msg(
                        Refuse(msg.job_id, msg.chunk_id), binary=speak_binary
                    ))
                    continue
                if isinstance(msg, RollAssign):
                    # extranonce-unit dispatch: expand against the cached
                    # template's nonce_bits — count whole segments, full
                    # 2^nonce_bits nonces each (protocol.RollAssign)
                    roll_chunk = True
                    lower, upper = chain.roll_span(
                        msg.extranonce0, msg.count, tmpl.nonce_bits
                    )
                else:
                    lower, upper = msg.lower, msg.upper
                msg = dc_replace(
                    tmpl, lower=lower, upper=upper, chunk_id=msg.chunk_id
                )
            if not isinstance(msg, Request):
                log.warning("worker: unexpected %s, dropping", type(msg).__name__)
                continue
            if msg.workload and workloads.maybe(msg.workload) is None:
                # a coordinator bug (we never advertised this workload)
                # or a registry drift across versions: Refuse so the
                # chunk requeues onto a capable worker instead of
                # wedging this one busy-forever on the books
                log.warning(
                    "worker: unregistered workload %r for job %d; "
                    "refusing chunk %d",
                    msg.workload, msg.job_id, msg.chunk_id,
                )
                client.write(encode_msg(
                    Refuse(msg.job_id, msg.chunk_id), binary=speak_binary
                ))
                continue

            # -- mine, keeping one read in flight for Cancel -------------
            # Generator steps run in an executor thread: a step may wait
            # for seconds (a long device sync) and must never block the
            # event loop — epoch heartbeats stopping would get this
            # worker declared dead. A step that HOLDS the GIL (a Pallas
            # lowering) starves the loop all the same, so the CLI's
            # device backends mine in a child process (miner_proc).
            loop = asyncio.get_running_loop()
            # roll-budget chunks: install a latest-value progress cell the
            # mining thread stores into (GIL-safe tuple write), and emit a
            # Beacon at most every beacon_interval seconds. Installed (or
            # cleared) unconditionally per chunk so a stale callback never
            # outlives its chunk.
            latest: dict = {}
            if roll_chunk and beacon_interval > 0:
                miner.progress_cb = (
                    lambda hw, n, h: latest.__setitem__("p", (hw, n, h))
                )
            else:
                miner.progress_cb = None
            last_beacon = mono()
            beacon_hw = -1
            if msg.workload:
                # the pluggable-workload compute seam (ISSUE 15): the
                # registered generator runs in the same executor loop,
                # same yield discipline, same Cancel window
                gen = miner.compute(msg)
            else:
                gen = miner.mine(msg)
            result: Optional[Result] = None
            cancelled = False
            _done = object()
            while True:
                # one step at a time: a ProcessMiner's steps receive on
                # its pipe, and two receiving threads would race
                step = loop.run_in_executor(None, next, gen, _done)
                while not step.done():
                    if read_task is None:
                        read_task = asyncio.ensure_future(client.read())
                    await asyncio.wait(
                        (step, read_task), return_when=asyncio.FIRST_COMPLETED
                    )
                    if not read_task.done():
                        continue
                    try:
                        raw = read_task.result()
                    except LspConnectionLost:
                        # stop the chunk and let its step return before
                        # leaving: a reconnect's first step must not
                        # receive on the miner beside this one
                        miner.cancel()
                        await asyncio.gather(step, return_exceptions=True)
                        await loop.run_in_executor(None, gen.close)
                        raise
                    read_task = None
                    note_codec(raw)
                    inner = _safe_decode(raw)
                    if (
                        not cancelled and isinstance(inner, Cancel)
                        and inner.job_id == msg.job_id
                    ):
                        cancelled = True
                        # this branch consumes the Cancel, so the
                        # top-level Cancel handler never sees it: evict
                        # the template HERE too. Any Assign of the dead
                        # job still queued behind this chunk (pipelined
                        # dispatch) then takes the Refuse seam instead
                        # of burning a whole chunk of device time on
                        # retired work. Do NOT purge the pending queue
                        # itself: a hedge-released job is still LIVE,
                        # and its post-Cancel re-dispatch (Setup +
                        # Assign, queued behind this Cancel) must
                        # survive — the in-order re-shipped Setup
                        # restores the template before that Assign is
                        # handled, while silently dropping it would
                        # wedge this worker busy-forever on the
                        # coordinator's books.
                        templates.pop(inner.job_id, None)
                        miner.cancel()
                    elif inner is not None:
                        pending.append(inner)
                item = step.result()
                if cancelled or item is _done:
                    # the generator is not resumed again: close it now
                    # (off the loop thread: its cleanup is miner code)
                    await loop.run_in_executor(None, gen.close)
                    break
                if item is not None:
                    result = item
                    break
                prog = latest.get("p")
                if (
                    prog is not None
                    and mono() - last_beacon >= beacon_interval
                ):
                    hw, bn, bh = prog
                    hw = min(hw, msg.upper)
                    # hw == upper means the chunk is done — the final
                    # Result (imminent) settles it; don't beacon
                    if msg.lower <= hw < msg.upper and hw > beacon_hw:
                        client.write(encode_msg(
                            Beacon(msg.job_id, msg.chunk_id, hw, bn, bh),
                            binary=speak_binary,
                        ))
                        last_beacon = mono()
                        beacon_hw = hw
            if result is None:
                log.info("worker: job %d cancelled mid-chunk", msg.job_id)
                continue
            if on_result is not None:
                on_result(result)
            client.write(encode_msg(result, binary=speak_binary))
            if (
                isinstance(result, Result) and result.found
                and result.mode.targeted
                and Coordinator._verify_result(msg, result)
            ):
                answered = msg.job_id
    except LspConnectionLost:
        log.info("worker: coordinator lost, exiting")
    finally:
        log.info(
            "worker: session ended; %d chunk(s) of answered jobs dropped "
            "unmined", dropped,
        )
        if read_task is not None:
            read_task.cancel()
        closer = getattr(miner, "close", None)
        if callable(closer):
            closer()  # e.g. ProfiledMiner flushes a still-open trace
        await client.close(drain_timeout=2.0)


async def run_miner_reconnect(
    host: str,
    port: int,
    miner: Miner,
    *,
    params: Optional[Params] = None,
    on_result: Optional[Callable[[Result], None]] = None,
    base_backoff: float = 0.2,
    max_backoff: float = 5.0,
    max_dials: Optional[int] = None,
    rng: Optional[random.Random] = None,
    binary: bool = True,
    addrs: Optional[list] = None,
    roll: bool = True,
    beacon_interval: float = 2.0,
    clock: Optional[Callable[[], float]] = None,
) -> None:
    """Worker serve loop that survives coordinator restarts (ISSUE 3).

    Runs :func:`run_miner`; when the coordinator is declared lost (or a
    dial fails), redials with jittered exponential backoff —
    ``base_backoff · 2^k``, capped at ``max_backoff``, each wait scaled
    by a uniform [0.5, 1.5) jitter so a whole fleet killed by one
    coordinator crash does not redial in lockstep — and re-``Join``s.
    The LSP boot epoch in the connect-ack guarantees the new session
    shares no sequence state with the old one, and a restarted
    coordinator re-ships every job template via the normal Setup path,
    so resumption needs no worker-side state at all.

    ``addrs`` (ISSUE 5, ``--coordinator host:port,host:port``) lists
    every coordinator address, primary first, standbys after: each
    failure — a failed dial or a lost session — rotates to the next
    address, so a fleet reaches a promoted standby with no new
    machinery (an un-promoted standby rejects the dial via the RESET
    path, which just advances the rotation). When given, it supersedes
    ``host``/``port``.

    A session that actually served (the connection was established)
    resets the backoff. ``max_dials`` bounds the loop for tests; the
    production CLI runs it unbounded (cancel the task to stop).
    """
    from tpuminter.replication import dial_patience

    targets = list(addrs) if addrs else [(host, port)]
    connect_epochs = dial_patience(targets)
    delays = jittered_backoff(base_backoff, max_backoff, rng)
    dials = 0
    while True:
        h, p = targets[dials % len(targets)]
        dials += 1
        try:
            await run_miner(
                h, p, miner, params=params, on_result=on_result,
                binary=binary, connect_epochs=connect_epochs,
                roll=roll, beacon_interval=beacon_interval, clock=clock,
            )
            # had a live session: fresh backoff episode
            delays = jittered_backoff(base_backoff, max_backoff, rng)
        except LspConnectError:
            pass  # dial failed: coordinator still down, keep backing off
        if max_dials is not None and dials >= max_dials:
            return
        wait = next(delays)
        log.info(
            "worker: coordinator gone; redialing %s:%d in %.2fs "
            "(attempt %d)",
            *targets[dials % len(targets)], wait, dials + 1,
        )
        await _sleep_on(clock, wait)


async def _sleep_on(
    clock: Optional[Callable[[], float]], seconds: float
) -> None:
    """Sleep ``seconds`` as measured by ``clock`` (the worker-side
    chaos seam, ISSUE 20): a drifting clock stretches or shrinks the
    real wait — which is the point, the backoff schedule must only
    ever degrade to a delayed (or hastened, still jitter-bounded)
    redial. Without a seam this is a plain sleep."""
    if clock is None:
        await asyncio.sleep(seconds)
        return
    start = clock()
    while True:
        remaining = seconds - (clock() - start)
        if remaining <= 0:
            return
        await asyncio.sleep(min(0.05, max(0.001, remaining)))


def _safe_decode(raw: bytes) -> Optional[Message]:
    try:
        return decode_msg(raw)
    except ProtocolError as exc:
        log.warning("worker: dropping malformed message: %s", exc)
        return None


def _build_miner(
    backend: str,
    *,
    exact_min: bool = False,
    slab: Optional[int] = None,
    depth: Optional[int] = None,
    spmd_leader: bool = False,
    roll_batch: Optional[int] = None,
) -> Miner:
    """Backend registry for the CLI; device backends import lazily.

    ``exact_min``/``slab``/``depth``/``roll_batch`` tune the device
    backends (ADVICE.md r2: fleets needing CpuMiner-compatible
    exhausted-range minima opt in via ``--exact-min``); the other
    backends ignore them.
    """
    if backend == "cpu":
        return CpuMiner()
    if backend == "jax":
        from tpuminter.jax_worker import JaxMiner

        kwargs = {}
        if roll_batch is not None:
            kwargs["roll_batch"] = roll_batch
        return JaxMiner(**kwargs)
    if backend == "tpu":
        from tpuminter.tpu_worker import TpuMiner

        kwargs = {"exact_min": exact_min}
        if slab is not None:
            kwargs["slab"] = slab
        if depth is not None:
            kwargs["depth"] = depth
        if roll_batch is not None:
            kwargs["roll_batch"] = roll_batch
        return TpuMiner(**kwargs)
    if backend == "pod":
        from tpuminter.pod_worker import PodMiner

        kwargs = {"exact_min": exact_min, "spmd_leader": spmd_leader}
        if slab is not None:
            kwargs["slab_per_device"] = slab
        if depth is not None:
            kwargs["depth"] = depth
        if roll_batch is not None:
            kwargs["roll_batch"] = roll_batch
        return PodMiner(**kwargs)
    if backend == "native":
        from tpuminter.native_worker import NativeMiner

        return NativeMiner()
    raise SystemExit(
        f"unknown backend {backend!r} (expected cpu|jax|tpu|pod|native)"
    )


def main(argv: Optional[list] = None) -> None:
    """CLI: ``python -m tpuminter.worker <host:port> [--backend cpu]``
    (≙ reference ``./miner <host:port>``)."""
    import argparse

    parser = argparse.ArgumentParser(description="tpuminter worker (miner role)")
    parser.add_argument(
        "hostport", nargs="?", default=None,
        help="coordinator address, host:port (or use --coordinator)",
    )
    parser.add_argument(
        "--coordinator", metavar="LIST", default=None,
        help="coordinator address list, host:port[,host:port...] — "
        "primary first, hot standbys after; with --reconnect each "
        "failure rotates to the next address, so the fleet lands on a "
        "promoted standby by itself (README 'Replication')",
    )
    parser.add_argument(
        "--backend", default="cpu",
        help="cpu|jax|tpu|pod|native (default cpu; pod drives every chip "
        "of the local slice as one worker; native is the compiled C++ loop)",
    )
    parser.add_argument(
        "--exact-min", action="store_true",
        help="tpu/pod backends: track the exact exhausted-range minimum "
        "(CpuMiner-compatible) at reduced throughput",
    )
    parser.add_argument(
        "--slab", type=int, default=None,
        help="tpu backend: nonces per device call (default 2^27)",
    )
    parser.add_argument(
        "--depth", type=int, default=None,
        help="tpu backend: device calls kept in flight (default 2)",
    )
    parser.add_argument(
        "--roll-batch", type=int, default=None,
        help="jax/tpu/pod backends: extranonce rows per rolled dispatch "
        "(default 8) — one batched roll + one batched sweep cover that "
        "many segments' worth of indices per device call (README "
        "'Rolled sweeps')",
    )
    parser.add_argument(
        "--profile", metavar="DIR", default=None,
        help="record a jax.profiler trace of the first mined chunk "
        "into DIR (viewable with tensorboard/xprof)",
    )
    parser.add_argument(
        "--beacon-interval", type=float, default=2.0, metavar="SECS",
        help="minimum seconds between sub-chunk progress beacons on a "
        "roll-budget chunk (default 2.0; <= 0 disables emission — the "
        "coordinator then sees no progress until the final Result)",
    )
    parser.add_argument(
        "--no-roll", action="store_true",
        help="do not advertise the roll-budget dialect: this worker only "
        "ever receives classic global-index Assigns (the interop "
        "'old peer' stand-in; README 'Roll-budget chunks')",
    )
    parser.add_argument(
        "--dev-lanes", choices=("auto", "on", "off"), default=None,
        help="hashcore workload chunks: compute on u32-pair device "
        "lanes (jnp/Pallas, ops.splitmix) instead of numpy host lanes. "
        "auto = device lanes on jax/tpu/pod backends only (the "
        "default); off is the bit-for-bit host-lane A/B baseline "
        "(README 'Device-lane workloads')",
    )
    parser.add_argument(
        "--codec", choices=("binary", "json"), default="binary",
        help="wire codec advertised to the coordinator (binary = the "
        "struct-packed fast path, negotiated — an old coordinator "
        "still gets JSON; json pins this worker to the compat path)",
    )
    parser.add_argument(
        "--reconnect", action="store_true",
        help="survive coordinator restarts: when the coordinator is "
        "declared lost, redial with jittered exponential backoff and "
        "re-Join instead of exiting (pairs with the coordinator's "
        "--journal crash recovery)",
    )
    args = parser.parse_args(argv)
    from tpuminter.replication import parse_addr_list

    if args.coordinator is not None:
        addrs = parse_addr_list(args.coordinator)
    elif args.hostport is not None:
        addrs = parse_addr_list(args.hostport)
    else:
        parser.error("need a coordinator address (positional or --coordinator)")
    if len(addrs) > 1 and not args.reconnect:
        parser.error(
            "an address list only makes sense with --reconnect (the "
            "rotation happens on redial)"
        )
    host, port = addrs[0]
    logging.basicConfig(level=logging.INFO)
    if args.backend in ("jax", "tpu", "pod"):
        # the device miner lives in a child process: lowering a Pallas
        # kernel holds the GIL for seconds, which would starve this
        # process's LSP heartbeats (miner_proc). This process never
        # imports jax, so the child is the one that holds the chip.
        from tpuminter.miner_proc import ProcessMiner, device_miner

        miner = ProcessMiner(
            device_miner, args.backend, dev_lanes=args.dev_lanes,
            profile=args.profile, exact_min=args.exact_min, slab=args.slab,
            depth=args.depth, roll_batch=args.roll_batch,
        )
        if miner.follower:  # a multi-host pod's follower: no session
            miner.join()
            return
    else:
        if args.dev_lanes is not None:
            from tpuminter.workloads import hashcore

            hashcore.set_dev_lanes(args.dev_lanes)
        miner = _build_miner(args.backend)
        if args.profile:
            try:
                import jax  # noqa: F401  (fail at startup, not mid-chunk)
            except ImportError as exc:
                raise SystemExit(
                    "--profile needs jax (the cpu backend itself does "
                    f"not); import failed: {exc}"
                )
            miner = ProfiledMiner(miner, args.profile)
    try:
        if args.reconnect:
            asyncio.run(run_miner_reconnect(
                host, port, miner, binary=args.codec == "binary",
                addrs=addrs, roll=not args.no_roll,
                beacon_interval=args.beacon_interval,
            ))
        else:
            asyncio.run(run_miner(
                host, port, miner, binary=args.codec == "binary",
                roll=not args.no_roll, beacon_interval=args.beacon_interval,
            ))
    finally:
        shutdown = getattr(miner, "shutdown", None)
        if callable(shutdown):
            shutdown()


if __name__ == "__main__":
    main()
