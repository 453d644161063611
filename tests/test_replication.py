"""Replicated-coordinator tests (ISSUE 5's fault-injection layer).

Same strata as tests/test_recovery.py:

- **Pure shipping-stream properties** (deterministic seeded drives;
  hypothesis mirrors live in tests/test_properties.py, absent in this
  image): a truncated/corrupted shipped batch only ever loses a
  suffix — the standby applies an exact record prefix, never different
  records — and incremental shadow apply equals full replay.
- **Shipping runtime**: primary→standby WAL shipping builds a shadow
  equal to replaying the primary's file; a standby restart resumes
  from its durable cursor and replays no record twice; the serve-tick
  journal flusher writes what the task flusher wrote.
- **Failover e2e**: the fencing regression (a restarted old primary's
  datagram draws RESET and its connection is declared lost — alongside
  test_recovery.py's fresh-session pin), the replica-ack gate, the
  SlowMiner failover drill (kill the primary machine mid-job; the
  promoted standby answers both bound clients exactly once with
  brute-force-equal results), and the loadgen failover scenario's
  tier-1 gate.
"""

import asyncio
import os
import random
import sys
import time

import pytest

sys.path.insert(
    0,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ),
)

import loadgen  # noqa: E402  (scripts/ is not a package)

from tpuminter.client import submit  # noqa: E402
from tpuminter.coordinator import Coordinator  # noqa: E402
from tpuminter.journal import (  # noqa: E402
    Journal,
    RecoveredState,
    encode_record,
    encode_settle,
    frame_payload,
    replay,
    scan,
    scan_with_cursor,
)
from tpuminter.protocol import (  # noqa: E402
    PowMode,
    Request,
    request_to_obj,
)
from tpuminter.replication import (  # noqa: E402
    FENCE_JUMP,
    ReplicationPrimary,
    ReplicationStandby,
    gate_any,
    parse_addr_list,
)
from tpuminter.worker import run_miner_reconnect  # noqa: E402

from tests.test_e2e import FAST, brute_min, run  # noqa: E402
from tests.test_recovery import SlowMiner  # noqa: E402


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _req_obj(jid, upper=4095, ckey=""):
    return request_to_obj(Request(
        job_id=jid, mode=PowMode.MIN, lower=0, upper=upper,
        data=b"rep-%d" % jid, client_key=ckey,
    ))


def _record_stream(rng, n=30):
    """A plausible journal byte stream: jobs, packed settles, finishes
    (ground-truth records come back out via ``scan``)."""
    blobs = []
    for jid in range(1, n + 1):
        blobs.append(encode_record({"k": "job", "id": jid,
                                    "req": _req_obj(jid)}))
        lo = rng.randrange(0, 2048)
        blobs.append(frame_payload(
            encode_settle(jid, lo, lo + 511, lo, 512, rng.randrange(2**64))
        ))
        if rng.random() < 0.3:
            blobs.append(encode_record(
                {"k": "finish", "id": jid, "ckey": f"c{jid}", "cjid": jid,
                 "mode": "min", "n": lo, "h": "ab", "found": True, "s": 512}
            ))
    return b"".join(blobs)


async def _drain(coro_or_task):
    coro_or_task.cancel()
    await asyncio.gather(coro_or_task, return_exceptions=True)


# ---------------------------------------------------------------------------
# pure shipping-stream properties (deterministic; hypothesis mirrors in
# tests/test_properties.py)
# ---------------------------------------------------------------------------

def test_corrupted_shipped_batch_applies_only_an_exact_prefix():
    """A single-byte flip anywhere in a shipped batch may end the
    readable stream, but what DOES decode must be an exact record
    prefix of the original — corruption can only look like loss of a
    suffix, never like different records (the property the standby's
    ingestion leans on before touching its shadow state)."""
    rng = random.Random(0x5EED)
    for trial in range(40):
        stream = _record_stream(rng, n=rng.randrange(2, 12))
        clean_records, clean = scan(stream)
        assert clean == len(stream)
        wire = bytearray(stream)
        i = rng.randrange(len(wire))
        wire[i] ^= rng.randrange(1, 256)
        got, got_clean, _last = scan_with_cursor(bytes(wire))
        assert got_clean <= clean
        assert got == clean_records[: len(got)], (
            f"trial {trial}: flip at {i} produced records that are not "
            f"an exact prefix"
        )


def test_truncated_shipped_batch_applies_only_an_exact_prefix():
    rng = random.Random(0xCAFE)
    for _ in range(40):
        stream = _record_stream(rng, n=rng.randrange(2, 12))
        clean_records, _ = scan(stream)
        keep = rng.randrange(len(stream))
        got, got_clean, _last = scan_with_cursor(stream[:keep])
        assert got_clean <= keep
        assert got == clean_records[: len(got)]


def test_incremental_shadow_apply_equals_full_replay():
    """The standby applies records batch-by-batch as they arrive; the
    result must equal replaying the whole stream at once, however the
    batch boundaries fall (including mid-record splits, which the
    contiguity check re-ships)."""
    rng = random.Random(7)
    for _ in range(20):
        stream = _record_stream(rng, n=rng.randrange(3, 15))
        records, _ = scan(stream)
        shadow = RecoveredState()
        i = 0
        while i < len(records):
            step = rng.randrange(1, 5)
            for rec in records[i : i + step]:
                shadow.apply(rec)
            i += step
        full = replay(records)
        assert shadow.jobs.keys() == full.jobs.keys()
        for jid, job in full.jobs.items():
            assert shadow.jobs[jid].remaining == job.remaining
            assert shadow.jobs[jid].best == job.best
        assert shadow.winners == full.winners
        assert shadow.next_job_id == full.next_job_id


# ---------------------------------------------------------------------------
# shipping runtime
# ---------------------------------------------------------------------------

def test_shipping_builds_a_shadow_equal_to_replaying_the_primary(tmp_path):
    pwal = str(tmp_path / "p.wal")
    swal = str(tmp_path / "s.wal")

    async def scenario():
        journal, _ = Journal.open(pwal)
        standby = await ReplicationStandby.create(swal, params=FAST)
        runner = asyncio.ensure_future(standby.run())
        prim = ReplicationPrimary(journal, "127.0.0.1", standby.port,
                                  params=FAST)
        prim.start()
        for jid in range(1, 40):
            journal.append("job", {"id": jid, "req": _req_obj(jid)})
        await journal.flush()
        t0 = time.monotonic()
        while standby.size < journal.size:
            assert time.monotonic() - t0 < 15, "shipping stalled"
            await asyncio.sleep(0.02)
        with open(pwal, "rb") as fh:
            records, clean = scan(fh.read())
        full = replay(records)
        assert standby.shadow.jobs.keys() == full.jobs.keys()
        assert standby.size == clean == journal.size
        # the local copy is byte-identical to the primary's clean prefix
        with open(swal, "rb") as fh:
            assert scan(fh.read())[1] == clean
        assert prim.synced and prim.acked == journal.size
        await prim.stop()
        await _drain(runner)
        await standby.close()
        await journal.aclose()

    run(scenario(), timeout=30.0)


def test_chained_standbys_cost_the_primary_one_stream(tmp_path):
    """primary → s1 → s2: the primary ships its WAL exactly once
    (``bytes_shipped`` equals its journal's size) while the tail hop
    still receives the whole log — the re-ship to s2 comes out of s1's
    budget, however deep the chain."""

    async def scenario():
        journal, _ = Journal.open(str(tmp_path / "p.wal"))
        s2 = await ReplicationStandby.create(
            str(tmp_path / "s2.wal"), params=FAST)
        s1 = await ReplicationStandby.create(
            str(tmp_path / "s1.wal"), params=FAST,
            chain_to=[("127.0.0.1", s2.port)])
        runners = [asyncio.ensure_future(s.run()) for s in (s2, s1)]
        prim = ReplicationPrimary(journal, "127.0.0.1", s1.port,
                                  params=FAST)
        prim.start()
        for jid in range(1, 120):
            journal.append("job", {"id": jid, "req": _req_obj(jid)})
        await journal.flush()
        t0 = time.monotonic()
        while s2.size < journal.size:
            assert time.monotonic() - t0 < 15, "chained shipping stalled"
            await asyncio.sleep(0.02)
        assert s1.size == s2.size == journal.size
        assert prim.stats["bytes_shipped"] == journal.size
        await prim.stop()
        for r in runners:
            await _drain(r)
        for s in (s1, s2):
            await s.close()
        await journal.aclose()

    run(scenario(), timeout=30.0)


def test_cursor_resume_after_standby_restart_replays_no_record_twice(
    tmp_path,
):
    """Kill the standby, restart it over the same local WAL: its
    SyncFrom cursor resumes the stream exactly where it stopped — the
    primary ships only the missed tail (no resync-from-0, no record
    applied twice), pinned by the applied-record count."""
    pwal = str(tmp_path / "p.wal")
    swal = str(tmp_path / "s.wal")

    async def scenario():
        journal, _ = Journal.open(pwal)
        standby = await ReplicationStandby.create(swal, params=FAST)
        runner = asyncio.ensure_future(standby.run())
        prim = ReplicationPrimary(journal, "127.0.0.1", standby.port,
                                  params=FAST)
        prim.start()
        for jid in range(1, 21):
            journal.append("job", {"id": jid, "req": _req_obj(jid)})
        await journal.flush()
        t0 = time.monotonic()
        while standby.size < journal.size:
            assert time.monotonic() - t0 < 15
            await asyncio.sleep(0.02)
        # -- standby dies --------------------------------------------------
        await prim.stop()
        await _drain(runner)
        await standby.close()
        # -- restart over the same file ------------------------------------
        standby2 = await ReplicationStandby.create(swal, params=FAST)
        applied_from_file = standby2.stats["records_applied"]
        runner2 = asyncio.ensure_future(standby2.run())
        prim2 = ReplicationPrimary(journal, "127.0.0.1", standby2.port,
                                   params=FAST)
        prim2.start()
        for jid in range(21, 31):
            journal.append("job", {"id": jid, "req": _req_obj(jid)})
        await journal.flush()
        t0 = time.monotonic()
        while standby2.size < journal.size:
            assert time.monotonic() - t0 < 15
            await asyncio.sleep(0.02)
        assert prim2.stats["resyncs"] == 0, (
            "a valid cursor must resume, not restart the stream"
        )
        shipped_new = standby2.stats["records_applied"] - applied_from_file
        assert shipped_new == 10, (
            f"exactly the 10 missed records must ship, got {shipped_new}"
        )
        with open(pwal, "rb") as fh:
            full = replay(scan(fh.read())[0])
        assert standby2.shadow.jobs.keys() == full.jobs.keys()
        await prim2.stop()
        await _drain(runner2)
        await standby2.close()
        await journal.aclose()

    run(scenario(), timeout=30.0)


def test_journal_flush_tick_writes_and_fires_durable_callbacks(tmp_path):
    """The serve-tick flusher (PERF.md §Round 10): with tick_flush on,
    nothing hits the disk until flush_tick (or the fallback timer)
    runs; callback-free batches write inline, durable batches still
    fsync and fire on_durable; the reopened journal replays
    identically to the task-flusher path."""
    path = str(tmp_path / "tick.wal")

    async def scenario():
        journal, _ = Journal.open(path)
        journal.tick_flush = True
        journal.append("job", {"id": 1, "req": _req_obj(1)})
        assert journal._buffer  # buffered, not yet written
        journal.flush_tick()
        assert not journal._buffer
        fired = []
        journal.append(
            "finish",
            {"id": 1, "ckey": "c", "cjid": 1, "mode": "min", "n": 3,
             "h": "ab", "found": True, "s": 4096},
            on_durable=lambda: fired.append(True),
        )
        journal.flush_tick()  # durable tier: task path + fsync
        await journal.flush()
        assert fired == [True]
        # the fallback timer covers appends with no serve tick behind
        # them (offloaded-verification settles)
        journal.append("abandon", {"id": 1})
        t0 = time.monotonic()
        while journal._buffer:
            assert time.monotonic() - t0 < 2.0, "fallback timer never fired"
            await asyncio.sleep(0.005)
        await journal.aclose()
        _journal2, state = Journal.open(path)
        await _journal2.aclose()
        assert state.finished == {1}
        assert ("c", 1) in state.winners

    run(scenario(), timeout=15.0)


def test_replica_ack_gate_parks_until_acked(tmp_path):
    """The replica-acked durability tier: with a synced standby the
    callback parks until the ack high-water passes the target; with no
    synced standby it fires immediately (availability over replica
    durability)."""
    pwal = str(tmp_path / "p.wal")

    async def scenario():
        journal, _ = Journal.open(pwal)
        prim = ReplicationPrimary(journal, "127.0.0.1", 1, params=FAST)
        fired = []
        # no synced session: release immediately
        gate_any([prim], 100, lambda: fired.append("now"))
        assert fired == ["now"]
        # synced session, ack behind the target: park, then release on
        # ack (_shipped bounds plausible acks — a real stream never
        # acks bytes it was not sent)
        prim.synced = True
        prim._shipped = 1000
        prim.acked = 50
        gate_any([prim], 100, lambda: fired.append("later"))
        assert fired == ["now"]
        prim._on_ack(99)
        assert fired == ["now"]
        prim._on_ack(100)
        assert fired == ["now", "later"]
        # session loss releases parked callbacks rather than wedging
        gate_any([prim], 500, lambda: fired.append("released"))
        prim._fire_gates("test teardown")
        assert fired == ["now", "later", "released"]
        await journal.aclose()

    run(scenario(), timeout=10.0)


def test_replica_ack_gate_survives_compaction_space_change(tmp_path):
    """A compaction swaps the journal's offset space (generation bump,
    size reset to the boot+snapshot length). Three hazards around the
    replica-ack tier, each pinned: a gate placed after the swap must
    not be released by the OLD space's ack high water; a stale
    old-space SyncAck arriving after the stream's resync must not
    poison the new space; and a gate placed before the swap re-bases
    to the end of the compacted file (the snapshot covers its record)
    instead of wedging behind an old-space byte target."""
    pwal = str(tmp_path / "p.wal")

    async def scenario():
        journal, state = Journal.open(pwal, compact_bytes=512, fsync=False)
        journal.snapshot_provider = lambda: state.snapshot_obj()
        prim = ReplicationPrimary(journal, "127.0.0.1", 1, params=FAST)
        fired = []
        # a synced stream that has shipped + acked the whole file
        prim.synced = True
        prim._shipped = journal.size
        prim.acked = journal.size
        # park a gate just past the ack high water, then drive a REAL
        # compaction underneath it
        gate_any([prim], journal.size + 1, lambda: fired.append("pre"))
        assert fired == []
        state.apply({"k": "job", "id": 1, "req": _req_obj(1)})
        journal.append("job", {"id": 1, "req": _req_obj(1)})
        for i in range(40):
            rec = {"k": "settle", "id": 1, "lo": 100 * i,
                   "hi": 100 * i + 49, "h": "ff", "n": 100 * i, "s": 50}
            state.apply(rec)
            journal.append("settle", dict(rec))
            await asyncio.sleep(0)
        await journal.flush()
        assert journal.stats["compactions"] >= 1
        assert journal.generation >= 1
        # (1) the journal moved ahead of the stream: a gate for the NEW
        # space must not be released by the old space's big ack value
        gate_any([prim], journal.size, lambda: fired.append("post"))
        assert fired == []
        # the shipping session notices the generation change (the real
        # resync path) ...
        prim._switch_generation()
        assert prim.acked == 0
        # (2) ... so a stale old-space ack arriving late is ignored
        prim._on_ack(10 ** 6)
        assert prim.acked == 0 and fired == []
        # (3) new-space acks release BOTH gates once the standby holds
        # the compacted file: the pre-compaction gate re-based to its
        # end rather than wedging at old-space byte `size + 1`
        prim._shipped = journal.size
        prim._on_ack(journal.size)
        assert sorted(fired) == ["post", "pre"]
        await journal.aclose()

    run(scenario(), timeout=10.0)


# ---------------------------------------------------------------------------
# fencing: the machine-loss sibling of test_recovery.py's
# test_server_restart_mid_connection_is_a_fresh_session
# ---------------------------------------------------------------------------

def test_restarted_old_primary_draws_reset_and_cannot_corrupt(tmp_path):
    """The acceptance regression: after failover, the OLD primary
    restarts from its own journal (epoch +1) and tries to resume
    shipping to the promoted standby. The promoted coordinator — whose
    epoch jumped FENCE_JUMP ahead — rejects the hello; the zombie's
    next datagram draws a RESET epoch-ack, its connection is declared
    lost, its shipping lane marks itself fenced, and the promoted
    state is untouched."""
    pwal = str(tmp_path / "p.wal")
    swal = str(tmp_path / "s.wal")

    async def scenario():
        standby = await ReplicationStandby.create(swal, params=FAST)
        runner = asyncio.ensure_future(standby.run())
        coord = await Coordinator.create(
            params=FAST, recover_from=pwal,
            replicate_to=[("127.0.0.1", standby.port)],
        )
        old_epoch = coord.boot_epoch
        serve = asyncio.ensure_future(coord.serve())
        # one journaled job so the promoted shadow is non-trivial
        journal = coord._journal
        journal.append("job", {"id": 1, "req": _req_obj(1, ckey="ck")})
        await journal.flush()
        t0 = time.monotonic()
        while standby.stats["records_applied"] < 2:  # boot + job
            assert time.monotonic() - t0 < 15, "shipping never started"
            await asyncio.sleep(0.02)
        # -- the primary machine dies -----------------------------------
        await _drain(serve)
        coord.crash()
        await asyncio.wait_for(
            standby.primary_lost.wait(),
            20 * FAST.epoch_limit * FAST.epoch_seconds,
        )
        coord2 = await standby.promote()
        assert coord2.boot_epoch >= old_epoch + FENCE_JUMP
        serve2 = asyncio.ensure_future(coord2.serve())
        jobs_before = set(coord2._jobs)
        # -- the old primary restarts and tries to resume its old role --
        zombie = await Coordinator.create(
            params=FAST, recover_from=pwal,
            replicate_to=[("127.0.0.1", coord2.port)],
        )
        assert zombie.boot_epoch == old_epoch + 1  # its own lineage
        serve3 = asyncio.ensure_future(zombie.serve())
        lane = zombie._replicas[0]
        t0 = time.monotonic()
        while not lane.fenced:
            assert time.monotonic() - t0 < 20, "zombie never fenced"
            await asyncio.sleep(0.05)
        # the loss was the RESET path, not a silence timeout
        assert "reset ack" in (lane.last_loss_reason or "") or (
            "restarted" in (lane.last_loss_reason or "")
        )
        assert coord2.stats["replication_fenced"] >= 1
        assert set(coord2._jobs) == jobs_before  # nothing corrupted
        await _drain(serve3)
        await _drain(serve2)
        await _drain(runner)
        await zombie.close()
        await coord2.close()

    run(scenario(), timeout=60.0)


# ---------------------------------------------------------------------------
# failover e2e (the acceptance drill, SlowMiner edition)
# ---------------------------------------------------------------------------

def test_failover_exactly_once_with_bound_clients(tmp_path):
    """Kill the primary MACHINE (its journal is never re-read) with a
    SlowMiner fleet and two bound clients mid-job; the standby promotes
    and the address-listed fleet lands on it — both clients get exactly
    one answer each, equal to brute force: no acknowledged work lost,
    no duplicate mining, zero manual intervention."""
    pwal = str(tmp_path / "p.wal")
    swal = str(tmp_path / "s.wal")
    upper = 8191
    payloads = [b"failover-client-a", b"failover-client-b"]

    async def scenario():
        standby = await ReplicationStandby.create(swal, params=FAST)
        runner = asyncio.ensure_future(standby.run())
        coord = await Coordinator.create(
            params=FAST, chunk_size=512, recover_from=pwal,
            replicate_to=[("127.0.0.1", standby.port)], replica_ack=True,
        )
        ports = [("127.0.0.1", coord.port), ("127.0.0.1", standby.port)]
        serve = asyncio.ensure_future(coord.serve())
        miners = [
            asyncio.ensure_future(run_miner_reconnect(
                "", 0, SlowMiner(), params=FAST, addrs=ports,
                base_backoff=0.05, max_backoff=0.4,
                rng=random.Random(100 + i),
            ))
            for i in range(3)
        ]
        await asyncio.sleep(0.2)
        subs = [
            asyncio.ensure_future(submit(
                "", 0, Request(job_id=70 + i, mode=PowMode.MIN, lower=0,
                               upper=upper, data=payloads[i]),
                params=FAST, client_key=f"failover-client-{i}",
                reconnect=True, base_backoff=0.05,
                rng=random.Random(i), addrs=ports,
            ))
            for i in range(2)
        ]
        serve2 = None
        try:
            t0 = time.monotonic()
            while coord.stats["results_accepted"] < 4:
                assert time.monotonic() - t0 < 20, "no progress pre-crash"
                await asyncio.sleep(0.01)
            assert coord.stats["jobs_done"] == 0, (
                "crash must land mid-job; slow the miners down"
            )
            # settles must actually have shipped (machine loss forgives
            # only the in-flight tail)
            t0 = time.monotonic()
            while standby.stats["records_applied"] < 4:
                assert time.monotonic() - t0 < 10, "shipping lagged"
                await asyncio.sleep(0.01)
            # -- the primary machine dies, journal and all ---------------
            await _drain(serve)
            coord.crash()
            await asyncio.wait_for(
                standby.primary_lost.wait(),
                20 * FAST.epoch_limit * FAST.epoch_seconds,
            )
            coord2 = await standby.promote(chunk_size=512)
            assert len(coord2._jobs) == 2, (
                "both mid-flight jobs must be live in the shadow"
            )
            assert sum(
                j.hashes_done for j in coord2._jobs.values()
            ) > 0, "shipped settles must survive into the shadow"
            serve2 = asyncio.ensure_future(coord2.serve())
            # -- the fleet lands on the promoted standby unattended ------
            results = await asyncio.wait_for(asyncio.gather(*subs), 90.0)
            for i, res in enumerate(results):
                expect = brute_min(payloads[i], 0, upper)
                assert (res.hash_value, res.nonce) == expect
                assert res.found
            assert not coord2._jobs  # both retired
        finally:
            for t in miners + subs:
                t.cancel()
            await asyncio.gather(*miners, *subs, return_exceptions=True)
            await _drain(runner)
            if serve2 is not None:
                await _drain(serve2)
                await coord2.close()

    run(scenario(), timeout=120.0)


# ---------------------------------------------------------------------------
# the loadgen failover scenario is the tier-1 gate (CI satellite)
# ---------------------------------------------------------------------------

def test_loadgen_failover_scenario_smoke(capsys):
    """`loadgen --scenario failover --smoke`: in-process primary kill +
    standby promotion under load must produce an exactly-once ledger
    and a takeover under one loss horizon — the replication sibling of
    the crash smoke gate."""
    rc = loadgen.main([
        "--scenario", "failover", "--smoke", "--json",
        "--miners", "6", "--duration", "1.5",
    ])
    out = capsys.readouterr()
    assert rc == 0, f"failover smoke failed:\n{out.out}\n{out.err}"


def test_parse_addr_list():
    assert parse_addr_list("a:1,b:2") == [("a", 1), ("b", 2)]
    assert parse_addr_list(":9000") == [("127.0.0.1", 9000)]
    with pytest.raises(ValueError):
        parse_addr_list(",")


def test_quota_buckets_survive_failover_promotion(tmp_path):
    """ISSUE 19 satellite: the ``quota`` record rides the replication
    WAL stream like every other append, so a promoted standby restores
    tenant budgets instead of resetting them — losing the primary
    MACHINE (its journal is never re-read) must not hand every tenant
    a fresh burst."""
    from tpuminter.journal import scan_file
    from tpuminter.lsp import LspClient
    from tpuminter.protocol import encode_msg

    pwal = str(tmp_path / "p.wal")
    swal = str(tmp_path / "s.wal")

    async def scenario():
        standby = await ReplicationStandby.create(swal, params=FAST)
        runner = asyncio.ensure_future(standby.run())
        coord = await Coordinator.create(
            params=FAST, chunk_size=512, recover_from=pwal,
            replicate_to=[("127.0.0.1", standby.port)], replica_ack=True,
            quota_rate=0.001, quota_burst=6,
        )
        serve = asyncio.ensure_future(coord.serve())
        coord2 = None
        client = None
        try:
            # no miners: this drill is about the admission ledger, the
            # submitted jobs just queue in the shadow
            client = await LspClient.connect(
                "127.0.0.1", coord.port, FAST
            )
            for jid in range(1, 5):
                client.write(encode_msg(Request(
                    job_id=jid, mode=PowMode.MIN, lower=0, upper=4095,
                    data=b"failover-quota-%d" % jid,
                    client_key="tenant-f",
                )))
            t0 = time.monotonic()
            while len(coord._jobs) < 4:
                assert time.monotonic() - t0 < 10, "submissions lost"
                await asyncio.sleep(0.01)
            tok, _, strikes = coord._buckets["tenant-f"]
            assert tok == pytest.approx(2.0, abs=0.01)
            coord._journal_quota()
            # the record must have SHIPPED (landed in the standby's
            # local WAL) before the machine dies — machine loss only
            # forgives the in-flight tail
            t0 = time.monotonic()
            while not replay(scan_file(swal)).quota:
                assert time.monotonic() - t0 < 10, "quota never shipped"
                await asyncio.sleep(0.02)
            # -- the primary machine dies, journal and all ---------------
            await _drain(serve)
            coord.crash()
            await asyncio.wait_for(
                standby.primary_lost.wait(),
                20 * FAST.epoch_limit * FAST.epoch_seconds,
            )
            coord2 = await standby.promote(
                quota_rate=0.001, quota_burst=6
            )
            assert "tenant-f" in coord2._buckets, (
                "the tenant's bucket must survive into the promotion"
            )
            tok2, _, strikes2 = coord2._buckets["tenant-f"]
            assert tok2 == pytest.approx(tok, abs=0.01)
            assert strikes2 == strikes
        finally:
            if client is not None:
                await client.close(drain_timeout=0.1)
            await _drain(runner)
            await _drain(serve)
            if coord2 is not None:
                await coord2.close()

    run(scenario(), timeout=90.0)
