"""The federation aggregator: a node that speaks WORKER upward and
COORDINATOR downward (ISSUE 18's tentpole).

Upward it is one LSP client session: it Joins the parent with the
aggregator hello (``Join.agg``), advertising the roll dialect and every
registered workload, and from then on looks exactly like one (large)
worker — it receives Setup/Assign/RollAssign/Cancel, answers with
Results, and reports rolled progress as Beacons. Downward it runs a
full, unmodified :class:`~tpuminter.coordinator.Coordinator` on its own
port and journal: the local fleet dials it like any coordinator, with
the whole protocol stack — carving, hedging, audits, the coverage-gated
fold registry, crash recovery — intact.

The seam between the two planes is the **lease**: each parent dispatch
becomes one inner job, submitted through a loopback client under this
aggregator's durable ``fed:<name>`` client key with the parent CHUNK id
as the client job id. That tuple is the exactly-once credential the
journal plane already enforces for ordinary clients — a re-submission
re-binds to the running inner job or answers from the winners table —
so cross-tier exactly-once is *composed* from the per-tier guarantee,
not re-implemented: every inner chunk settles exactly once into the
inner job's coverage ledger, and every inner job's final accumulator
settles exactly once into the parent's, including the non-idempotent
sum fold (each tier's coverage gate absorbs a given range once).

Control-cost shape: the parent sees ONE session, ONE Result per lease,
and at most one merged Beacon per lease per ``beacon_interval`` — the
beacon is computed from the inner job's books (settled prefix = min
lower bound over its remaining ranges, running best = the inner
min-fold), so parent-side control messages per settled segment stay
~constant as the local fleet grows (tests/test_federation.py pins it).

Failure matrix (all one-sided, nothing needs distributed agreement):

- *Aggregator crash mid-lease*: the parent sees the connection die and
  requeues the un-beaconed remainder (beaconed prefixes are already
  journaled settles). The restarted aggregator replays its journal,
  finds the open lease records, and DROPS them — abandoning the
  matching recovered inner jobs — because the parent may have re-leased
  the range to a sibling under a bumped epoch (federation.lease).
- *Parent connection loss*: every active lease is dropped the same way
  and the upward loop redials with jittered backoff through the address
  rotation (a promoted standby is just the next address).
- *Sibling steal*: an idle aggregator (fleet has capacity, nothing
  queued) sends ``Steal`` upward; the parent re-leases a slow sibling's
  un-beaconed suffix under a bumped lease epoch. The loser's late
  Beacons/Results carry the old epoch / a popped chunk id and are
  fenced at the parent — rejected, never double-counted.
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from collections import OrderedDict
from dataclasses import replace as dc_replace
from typing import Dict, List, Optional, Tuple

from tpuminter import chain, workloads
from tpuminter.analysis import affinity
from tpuminter.client import JobRefused, submit
from tpuminter.coordinator import Coordinator
from tpuminter.federation.lease import Lease, lease_end_record, lease_record
from tpuminter.lsp import LspClient, LspConnectError, LspConnectionLost, Params
from tpuminter.lsp.params import FAST, jittered_backoff
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
    Steal,
    WorkResult,
    decode_msg,
    encode_msg,
    payload_is_binary,
)

log = logging.getLogger(__name__)

__all__ = ["Aggregator"]

#: Parent job templates cached from Setups, oldest-evicted (same cap
#: and rationale as the worker's template table).
TEMPLATE_CAP = 256


class Aggregator:
    """One federation tier node. Use :meth:`create`; drive with
    :meth:`serve`; stop with :meth:`close`.

    Aggregator-side tables are bounded by construction: ``_templates``
    is capacity-evicted at :data:`TEMPLATE_CAP`; ``_leases`` /
    ``_lease_tasks`` / ``_beacon_hw`` hold one entry per outstanding
    parent dispatch (bounded by the parent's pipeline depth) and every
    exit path — finish, refuse, Cancel, parent loss, restart recovery —
    pops them (the bounded-state checker audits exactly this)."""

    def __init__(
        self,
        name: str,
        inner: Coordinator,
        targets: List[Tuple[str, int]],
        *,
        params: Optional[Params] = None,
        beacon_interval: float = 0.5,
        steal_interval: Optional[float] = None,
        lanes: int = 0,
        max_dials: Optional[int] = None,
        rng: Optional[random.Random] = None,
    ):
        if not name:
            raise ValueError("an aggregator needs a non-empty name")
        self.name = name
        self.inner = inner
        self._targets = list(targets)
        if not self._targets:
            raise ValueError("an aggregator needs at least one parent address")
        self._params = params or FAST
        self._beacon_interval = beacon_interval
        #: seconds between Steal hints while the fleet is idle; None
        #: disables stealing (the parent denies them anyway unless its
        #: own ``steal_after`` opt-in is set)
        self._steal_interval = steal_interval
        self._lanes = lanes
        self._max_dials = max_dials
        self._rng = rng
        #: this tier's durable client identity on the inner plane — the
        #: half of the cross-tier exactly-once credential this node owns
        self._ckey = f"fed:{name}"
        #: parent job_id → template Request (from Setup), size-capped
        self._templates: "OrderedDict[int, Request]" = OrderedDict()
        #: parent chunk_id → active Lease; one per outstanding parent
        #: dispatch, popped on every exit path
        self._leases: Dict[int, Lease] = {}
        #: parent chunk_id → the loopback submit task mining it
        self._lease_tasks: Dict[int, asyncio.Task] = {}
        #: parent chunk_id → last high-water beaconed upward (beacons
        #: must advance strictly; popped with the lease)
        self._beacon_hw: Dict[int, int] = {}
        self._client: Optional[LspClient] = None
        self._speak_binary = False
        self._stop = asyncio.Event()
        # loop-affinity stamp: the aggregator is a process-lifetime
        # control-plane object like Coordinator/Journal, so the runtime
        # race detector AND the bounded-state static checker (which
        # uses the stamp as its lifetime oracle) both cover its tables
        affinity.stamp(self)
        self.stats = {
            "leases_taken": 0,
            "leases_finished": 0,
            "leases_dropped": 0,
            "leases_refused": 0,
            "beacons_up": 0,
            "results_up": 0,
            "steals_sent": 0,
        }

    @classmethod
    async def create(
        cls,
        name: str,
        targets: List[Tuple[str, int]],
        *,
        inner_port: int = 0,
        params: Optional[Params] = None,
        recover_from: Optional[str] = None,
        beacon_interval: float = 0.5,
        steal_interval: Optional[float] = None,
        lanes: int = 0,
        max_dials: Optional[int] = None,
        rng: Optional[random.Random] = None,
        **inner_kwargs,
    ) -> "Aggregator":
        """Start the inner coordinator (journaled when ``recover_from``
        is given; extra kwargs pass through to
        :meth:`Coordinator.create`) and build the tier node around it.
        ``targets`` lists parent addresses, primary first — the upward
        loop rotates through them on every failure, which is the whole
        parent-failover story."""
        inner = await Coordinator.create(
            inner_port, params=params, recover_from=recover_from,
            **inner_kwargs,
        )
        self = cls(
            name, inner, targets, params=params,
            beacon_interval=beacon_interval, steal_interval=steal_interval,
            lanes=lanes, max_dials=max_dials, rng=rng,
        )
        self._drop_recovered_leases()
        return self

    # -- recovery --------------------------------------------------------

    def _drop_recovered_leases(self) -> None:
        """One-sided lease recovery (federation.lease): every lease
        that was open at the crash is dropped — its recovered inner job
        abandoned, its record closed — because the parent already saw
        the connection die and requeued the range, possibly to a
        sibling under a bumped epoch. Resuming would mine indices
        someone else now owns."""
        recs = self.inner.recovered_leases
        for pc in list(recs):
            lease = Lease.from_record(recs.pop(pc))
            jid = self.inner._bound.get((self._ckey, lease.parent_chunk_id))
            if jid is not None:
                self.inner._abandon_job(jid)
            self.inner._journal_append(
                "lease_end", lease_end_record(lease.parent_chunk_id)
            )
            self.stats["leases_dropped"] += 1
            log.info(
                "aggregator %s: dropped recovered lease for parent "
                "chunk %d (range [%d, %d])",
                self.name, lease.parent_chunk_id, lease.lower, lease.upper,
            )

    # -- lifecycle -------------------------------------------------------

    @property
    def port(self) -> int:
        """The DOWNWARD port the local fleet dials."""
        return self.inner.port

    async def serve(self) -> None:
        """Run both planes until cancelled or the dial budget runs out:
        the inner coordinator's serve loop and the upward worker-facing
        session (with redial)."""
        inner_task = asyncio.ensure_future(self.inner.serve())
        try:
            await self._upward_loop()
        finally:
            inner_task.cancel()
            try:
                await inner_task
            except (asyncio.CancelledError, Exception):
                pass

    async def close(self) -> None:
        self._stop.set()
        self._abandon_all_leases("aggregator closing")
        client = self._client
        if client is not None:
            self._client = None
            await client.close(drain_timeout=1.0)
        await self.inner.close()

    def crash(self) -> None:
        """kill -9 seam for the failure drills: both planes die with no
        goodbye — no lease_end records, no Refuse upward, buffered
        journal records lost. The restarted node
        (``create(recover_from=...)``) replays the open lease records
        and exercises the one-sided recovery (:meth:`_drop_recovered_leases`);
        the parent independently sees the session die and requeues."""
        self._stop.set()
        for task in self._lease_tasks.values():
            task.cancel()
        self._lease_tasks.clear()
        self._leases.clear()
        self._beacon_hw.clear()
        client = self._client
        if client is not None:
            self._client = None
            client.endpoint.close()
        self.inner.crash()

    # -- upward plane ----------------------------------------------------

    async def _upward_loop(self) -> None:
        from tpuminter.replication import dial_patience

        connect_epochs = dial_patience(self._targets)
        delays = jittered_backoff(0.2, 5.0, self._rng)
        dials = 0
        while not self._stop.is_set():
            host, port = self._targets[dials % len(self._targets)]
            dials += 1
            try:
                await self._session(host, port, connect_epochs)
                # had a live session: fresh backoff episode
                delays = jittered_backoff(0.2, 5.0, self._rng)
            except LspConnectError:
                pass  # parent (or this standby) not up yet: rotate on
            if self._stop.is_set():
                return
            if self._max_dials is not None and dials >= self._max_dials:
                return
            wait = next(delays)
            log.info(
                "aggregator %s: parent gone; redialing %s:%d in %.2fs",
                self.name, *self._targets[dials % len(self._targets)], wait,
            )
            await asyncio.sleep(wait)

    async def _session(self, host: str, port: int, connect_epochs) -> None:
        client = await LspClient.connect(
            host, port, self._params, connect_epochs=connect_epochs
        )
        self._client = client
        self._speak_binary = False
        miners = self.inner._miners.values()
        client.write(encode_msg(Join(
            backend="agg",
            # advertise the FLEET's aggregate throughput and widest
            # pipeline stage so the parent sizes leases for the whole
            # tier, not for one worker
            lanes=self._lanes or max(1, sum(m.lanes for m in miners)),
            span=max((m.span for m in self.inner._miners.values()), default=0),
            codec="bin", roll=True, workloads=workloads.names(),
            agg=self.name,
        )))
        ticker = asyncio.ensure_future(self._ticker(client))
        try:
            while True:
                raw = await client.read()
                if not self._speak_binary and payload_is_binary(raw):
                    # same negotiation as the worker: one binary payload
                    # from the parent proves it decodes binary
                    self._speak_binary = True
                try:
                    msg = decode_msg(raw)
                except ProtocolError as exc:
                    log.warning(
                        "aggregator %s: dropping malformed parent "
                        "message: %s", self.name, exc,
                    )
                    continue
                self._on_parent_message(client, msg)
        except LspConnectionLost:
            log.info("aggregator %s: parent session lost", self.name)
        finally:
            ticker.cancel()
            self._client = None
            # one-sided teardown, live edition: the parent declares us
            # lost and requeues every outstanding dispatch, so whatever
            # our fleet was mining for those leases is dead work now
            self._abandon_all_leases("parent session lost")
            await client.close(drain_timeout=1.0)

    def _on_parent_message(self, client: LspClient, msg: Message) -> None:
        if isinstance(msg, Setup):
            self._templates[msg.request.job_id] = msg.request
            while len(self._templates) > TEMPLATE_CAP:
                self._templates.popitem(last=False)
            return
        if isinstance(msg, Cancel):
            self._templates.pop(msg.job_id, None)
            for pc, lease in list(self._leases.items()):
                if lease.parent_job_id == msg.job_id:
                    self._drop_lease(pc, "parent Cancel")
            return
        if isinstance(msg, (Assign, RollAssign)):
            tmpl = self._templates.get(msg.job_id)
            if tmpl is None:
                # same self-healing seam as the worker: a silently
                # dropped dispatch would wedge this tier busy-forever
                # on the parent's books
                log.warning(
                    "aggregator %s: no template for parent job %d; "
                    "refusing chunk %d", self.name, msg.job_id, msg.chunk_id,
                )
                self._write_up(
                    client, Refuse(msg.job_id, msg.chunk_id)
                )
                return
            epoch = 0
            if isinstance(msg, RollAssign):
                lower, upper = chain.roll_span(
                    msg.extranonce0, msg.count, tmpl.nonce_bits
                )
                epoch = msg.lease_epoch
            else:
                lower, upper = msg.lower, msg.upper
            self._start_lease(client, tmpl, msg.chunk_id, lower, upper, epoch)
            return
        log.warning(
            "aggregator %s: unexpected %s from parent, dropping",
            self.name, type(msg).__name__,
        )

    def _write_up(self, client: LspClient, msg: Message) -> None:
        try:
            client.write(encode_msg(msg, binary=self._speak_binary))
        except ConnectionError:
            pass  # session is dying; the read loop will see it

    # -- leases ----------------------------------------------------------

    def _start_lease(
        self, client: LspClient, tmpl: Request,
        parent_chunk_id: int, lower: int, upper: int, epoch: int,
    ) -> None:
        if parent_chunk_id in self._leases:
            return  # duplicate dispatch (parent retransmit); one lease
        lease = Lease(
            parent_job_id=tmpl.job_id, parent_chunk_id=parent_chunk_id,
            lower=lower, upper=upper, lease_epoch=epoch,
        )
        self._leases[parent_chunk_id] = lease
        # durable BEFORE the first downward dispatch: a crash from here
        # on replays the open lease and tears it down observably
        self.inner._journal_append("lease", lease_record(lease))
        self.stats["leases_taken"] += 1
        # the inner job: the leased sub-range under OUR durable client
        # key and the parent chunk id — the (ckey, job_id) pair the
        # inner journal plane already makes exactly-once
        # stream=False on the inner submission (ISSUE 20): streaming
        # composes at LEASE granularity — each finished lease is a
        # journaled settle on the PARENT, which is what drives the
        # parent's own Emits — so inner partial Emits would only be
        # noise on this session's read loop, never forwarded
        req = dc_replace(
            tmpl, job_id=parent_chunk_id, lower=lower, upper=upper,
            chunk_id=0, client_key=self._ckey, stream=False,
        )
        self._lease_tasks[parent_chunk_id] = asyncio.ensure_future(
            self._run_lease(client, lease, req)
        )

    async def _run_lease(
        self, client: LspClient, lease: Lease, req: Request
    ) -> None:
        pc = lease.parent_chunk_id
        try:
            res = await submit(
                "127.0.0.1", self.inner.port, req,
                params=self._params, client_key=self._ckey,
            )
        except (JobRefused, LspConnectionLost, LspConnectError):
            # the inner plane cannot mine this lease (registry drift,
            # inner crash without a journal, ...): hand the range back
            # upward so the parent requeues it elsewhere
            self._lease_tasks.pop(pc, None)
            self._beacon_hw.pop(pc, None)
            if self._leases.pop(pc, None) is not None:
                self.inner._journal_append("lease_end", lease_end_record(pc))
                self.stats["leases_refused"] += 1
                self._write_up(client, Refuse(lease.parent_job_id, pc))
            return
        self._lease_tasks.pop(pc, None)
        self._beacon_hw.pop(pc, None)
        if self._leases.pop(pc, None) is None:
            return  # dropped while mining (Cancel/loss): answer is dead
        self.inner._journal_append("lease_end", lease_end_record(pc))
        self.stats["leases_finished"] += 1
        if isinstance(res, WorkResult):
            out: Message = WorkResult(
                job_id=lease.parent_job_id, chunk_id=pc, wid=res.wid,
                searched=res.searched, payload=res.payload,
            )
        else:
            out = Result(
                lease.parent_job_id, res.mode, res.nonce, res.hash_value,
                found=res.found, searched=res.searched, chunk_id=pc,
            )
        self.stats["results_up"] += 1
        self._write_up(client, out)

    def _drop_lease(self, parent_chunk_id: int, reason: str) -> None:
        lease = self._leases.pop(parent_chunk_id, None)
        if lease is None:
            return
        task = self._lease_tasks.pop(parent_chunk_id, None)
        if task is not None:
            task.cancel()
        self._beacon_hw.pop(parent_chunk_id, None)
        jid = self.inner._bound.get((self._ckey, parent_chunk_id))
        if jid is not None:
            self.inner._abandon_job(jid)
        self.inner._journal_append(
            "lease_end", lease_end_record(parent_chunk_id)
        )
        self.stats["leases_dropped"] += 1
        log.info(
            "aggregator %s: dropped lease for parent chunk %d (%s)",
            self.name, parent_chunk_id, reason,
        )

    def _abandon_all_leases(self, reason: str) -> None:
        for pc in list(self._leases):
            self._drop_lease(pc, reason)

    # -- merged beacons & stealing ---------------------------------------

    async def _ticker(self, client: LspClient) -> None:
        last_steal = time.monotonic()
        while True:
            await asyncio.sleep(self._beacon_interval)
            self._emit_beacons(client)
            if (
                self._steal_interval is not None
                and time.monotonic() - last_steal >= self._steal_interval
                and self._fleet_idle()
            ):
                last_steal = time.monotonic()
                self.stats["steals_sent"] += 1
                self._write_up(client, Steal())

    def _emit_beacons(self, client: LspClient) -> None:
        """One merged Beacon per rolled lease per tick, computed from
        the inner job's books: the settled prefix is everything below
        the lowest remaining lower bound (queued + in-flight +
        verifying — the same three places a journal snapshot reads),
        and the claimed pair is the inner min-fold. However many
        workers mine the lease, the parent sees at most one message
        per tick — the fan-in cost flattening."""
        for pc, lease in list(self._leases.items()):
            tmpl = self._templates.get(lease.parent_job_id)
            if tmpl is None or not tmpl.rolled or tmpl.mode == PowMode.SCRYPT:
                continue  # only rolled fast-dialect leases beacon
            jid = self.inner._bound.get((self._ckey, pc))
            job = self.inner._jobs.get(jid) if jid is not None else None
            if job is None or job.done:
                continue
            remaining = list(job.ranges)
            remaining.extend(
                (lo, hi) for (_conn, lo, hi) in job.inflight.values()
            )
            remaining.extend(job.verifying)
            if not remaining:
                continue  # fully swept: the final Result is imminent
            hw = min(lo for lo, _hi in remaining) - 1
            if not lease.lower <= hw < lease.upper:
                continue
            if hw <= self._beacon_hw.get(pc, lease.lower - 1):
                continue  # no NEW settled prefix since the last tick
            if job.best is not None:
                bh, bn = job.best
            else:
                bh, bn = MIN_UNTRACKED, 0
            self._write_up(client, Beacon(
                lease.parent_job_id, pc, hw, bn, bh,
                lease_epoch=lease.lease_epoch,
            ))
            self._beacon_hw[pc] = hw
            self.stats["beacons_up"] += 1

    def _fleet_idle(self) -> bool:
        """True when the local fleet could absorb more work right now:
        someone is idle and every active lease is fully dispatched.
        The Steal this gates is only a hint — the parent applies its
        own ``steal_after`` policy."""
        inner = self.inner
        if not inner._miners or not inner._idle:
            return False
        return all(
            not job.ranges for job in inner._jobs.values() if not job.done
        )


def main(argv: Optional[list] = None) -> None:
    """``python -m tpuminter.federation.aggregator NAME --coordinator
    host:port[,host:port...]`` — run one federation tier node: dial the
    parent(s) as a worker, serve the local fleet as a coordinator on
    ``--port``."""
    import argparse

    parser = argparse.ArgumentParser(
        description="tpuminter federation aggregator (worker upward, "
        "coordinator downward)"
    )
    parser.add_argument(
        "name", help="stable tier identity — the durable client key "
        "fed:<name> on the inner plane; keep it constant across "
        "restarts or recovery dedup is lost",
    )
    parser.add_argument(
        "--coordinator", required=True, metavar="HOST:PORT[,...]",
        help="parent address list, primary first; each upward failure "
        "rotates to the next (the parent-failover story)",
    )
    parser.add_argument(
        "--port", type=int, default=0,
        help="DOWNWARD port the local fleet dials (0 = ephemeral, "
        "logged at startup)",
    )
    parser.add_argument(
        "--journal", metavar="PATH", default=None,
        help="inner WAL — makes parent leases durable and the inner "
        "exactly-once plane crash-safe",
    )
    parser.add_argument(
        "--beacon-interval", type=float, default=0.5, metavar="SECONDS",
        help="merged upward Beacon cadence (the parent's control cost "
        "per tier is ~1/interval regardless of local fleet size)",
    )
    parser.add_argument(
        "--steal-interval", type=float, default=None, metavar="SECONDS",
        help="send Steal hints this often while the local fleet is "
        "idle (default: never; the parent also ignores them unless "
        "its own --steal-after is armed)",
    )
    parser.add_argument(
        "--roll-budget", type=int, default=16, metavar="N",
        help="extranonce segments per inner RollAssign (passed to the "
        "inner coordinator)",
    )
    parser.add_argument(
        "--lanes", type=int, default=0,
        help="lane width advertised upward (0 = sum of the local "
        "fleet's lanes, re-advertised as they join)",
    )
    args = parser.parse_args(argv)
    targets = []
    for addr in args.coordinator.split(","):
        host, _, port = addr.strip().rpartition(":")
        targets.append((host or "127.0.0.1", int(port)))
    logging.basicConfig(level=logging.INFO)

    async def _run() -> None:
        agg = await Aggregator.create(
            args.name, targets, inner_port=args.port,
            recover_from=args.journal,
            beacon_interval=args.beacon_interval,
            steal_interval=args.steal_interval,
            lanes=args.lanes, roll_budget=args.roll_budget,
        )
        log.info(
            "aggregator %s: fleet port %d, parents %s",
            args.name, agg.port, targets,
        )
        try:
            await agg.serve()
        finally:
            await agg.close()

    asyncio.run(_run())


if __name__ == "__main__":
    main()
