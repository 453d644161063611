"""Federation tier tests (ISSUE 18).

The tentpole drills: a two-tier tree (client → parent coordinator →
aggregator → local fleet) mines a rolled TARGET job to the exact
brute-forced minimum; the exactly-once ledger holds across an
aggregator crash mid-lease, a sibling steal of an un-beaconed suffix,
and a parent failover to a promoted standby. Around them, the
unit layers one seam at a time:

- codec: the epoch-bearing RollAssign/Beacon binary variants, the
  aggregator Join fallback, the JSON-only Steal;
- policy: ``federation.steal.pick_victim`` against hand-built books,
  the bounded StolenRegistry;
- durability: lease records through journal replay, and the restarted
  aggregator's one-sided drop of recovered leases;
- folds (satellite): two-level ``tree_merge`` equals the flat fold for
  every discipline, under duplicate delivery, replay, and
  partial-coverage reporting;
- transport (satellite): the slow-loris read/first-message deadlines
  at the ConnState layer — total-time bounds that byte-per-epoch
  drip-feeding cannot evade;
- scale (satellite): >= 20k durable ckeys through the quota and
  winner/dedup tables stay inside their caps (100k behind ``-m slow``);
- WAL bound (satellite): live compaction keeps a writer-mode journal
  file bounded under sustained load.
"""

import asyncio
import dataclasses
import os
import random
import time
from collections import OrderedDict

import pytest

from tpuminter.client import JobRefused, submit
from tpuminter.coordinator import QUOTA_BUCKETS_CAP, Coordinator
from tpuminter.federation import steal as fsteal
from tpuminter.federation.aggregator import Aggregator
from tpuminter.federation.lease import Lease, lease_end_record, lease_record
from tpuminter.journal import Journal, replay
from tpuminter.lsp import LspConnectError, LspConnectionLost
from tpuminter.lsp.connection import _MORE, ConnState
from tpuminter.lsp.message import Frame, MsgType
from tpuminter.lsp.params import Params
from tpuminter.protocol import (
    Beacon,
    Join,
    PowMode,
    RollAssign,
    Steal,
    decode_msg,
    encode_msg,
    payload_is_binary,
)
from tpuminter.worker import CpuMiner, run_miner
from tpuminter.workloads import folds as wfolds

from tests.test_e2e import FAST, run
from tests.test_extranonce import fixture
from tests.test_roll_budget import NB, _brute, _rolled_request


# ---------------------------------------------------------------------------
# codec: the epoch-bearing wire variants
# ---------------------------------------------------------------------------

def test_rollassign_and_beacon_epoch_variants_roundtrip_binary():
    for msg in (
        RollAssign(3, 17, 5, 4, lease_epoch=9),
        RollAssign(3, 17, 5, 4),  # epoch 0: the legacy tag
        Beacon(3, 17, 5000, 42, 0xDEAD, lease_epoch=2),
        Beacon(3, 17, 5000, 42, 0xDEAD),
    ):
        raw = encode_msg(msg, binary=True)
        assert payload_is_binary(raw)
        assert decode_msg(raw) == msg
        # JSON stays the universal fallback
        assert decode_msg(encode_msg(msg, binary=False)) == msg


def test_aggregator_join_falls_back_to_json_and_steal_roundtrips():
    join = Join(backend="agg", lanes=8, codec="bin", roll=True, agg="a1")
    raw = encode_msg(join, binary=True)
    # the binary Join layout predates the agg field: composing tiers
    # must not silently drop the hello, so it rides JSON
    assert not payload_is_binary(raw)
    assert decode_msg(raw) == join
    for steal in (Steal(), Steal(job_id=7)):
        assert decode_msg(encode_msg(steal, binary=True)) == steal


# ---------------------------------------------------------------------------
# policy: pick_victim against hand-built books
# ---------------------------------------------------------------------------

class _M:
    def __init__(self, conn_id, chunks):
        self.conn_id = conn_id
        self.chunks = OrderedDict(chunks)


class _J:
    def __init__(self, request, done=False):
        self.request = request
        self.done = done


def _books(steal_after=0.5, now=100.0):
    seg = 1 << NB
    req = _rolled_request(8, target=1)
    jobs = {1: _J(req)}
    # conn 10 holds a stalled whole-segment chunk (cid 100, age 10s)
    # and a FRESH one (cid 101); conn 20 (the thief) holds its own
    miners = {
        10: _M(10, {
            100: (1, 0, 4 * seg - 1, now - 10.0),
            101: (1, 4 * seg, 8 * seg - 1, now - 0.1),
        }),
        20: _M(20, {102: (1, 8 * seg, 12 * seg - 1, now - 10.0)}),
    }
    return miners, jobs, req, seg


def test_pick_victim_takes_the_oldest_stalled_whole_segment_chunk():
    miners, jobs, _req, seg = _books()
    got = fsteal.pick_victim(
        miners, jobs, {}, thief_conn=20, steal_after=0.5, now=100.0
    )
    assert got == (10, 100, 1, 0, 4 * seg - 1)


def test_pick_victim_denials():
    miners, jobs, req, seg = _books()
    deny = dict(thief_conn=20, steal_after=0.5, now=100.0)
    # never rob yourself: the only other holder is the thief
    assert fsteal.pick_victim(
        {20: miners[20]}, jobs, {}, **deny
    ) is None
    # audits are evidence, not capacity
    assert fsteal.pick_victim(
        miners, jobs, {100: object(), 101: object()}, **deny
    ) is None
    # a beaconing (fresh-progress) holder is not a straggler
    fresh = {10: _M(10, {100: (1, 0, 4 * seg - 1, 99.9)})}
    assert fsteal.pick_victim(fresh, jobs, {}, **deny) is None
    # done job / unknown job
    assert fsteal.pick_victim(
        miners, {1: _J(req, done=True)}, {}, **deny
    ) is None
    # sub-segment suffix finishes sooner than a re-lease round-trips
    subseg = {10: _M(10, {100: (1, 0, seg - 2, 90.0)})}
    assert fsteal.pick_victim(subseg, jobs, {}, **deny) is None
    # non-rolled and scrypt jobs never qualify
    flat = dataclasses.replace(req, coinbase_prefix=None, target=1)
    assert fsteal.pick_victim(miners, {1: _J(flat)}, {}, **deny) is None
    # job_id filter narrows the hunt
    assert fsteal.pick_victim(
        miners, jobs, {}, job_id=2, **deny
    ) is None


def test_stolen_registry_is_bounded_and_remembers_newest():
    reg = fsteal.StolenRegistry(cap=4)
    for cid in range(10):
        reg.add(cid, lease_epoch=cid + 1)
    assert len(reg) == 4
    assert 9 in reg and 6 in reg
    assert 5 not in reg and 0 not in reg
    with pytest.raises(ValueError):
        fsteal.StolenRegistry(cap=0)


# ---------------------------------------------------------------------------
# durability: lease records through replay; one-sided drop on restart
# ---------------------------------------------------------------------------

def test_lease_records_replay_open_leases_only():
    l1 = Lease(parent_job_id=5, parent_chunk_id=100, lower=0,
               upper=4095, lease_epoch=2, inner_job_id=9)
    l2 = Lease(parent_job_id=5, parent_chunk_id=101, lower=4096,
               upper=8191)
    assert Lease.from_record(lease_record(l1)) == l1
    records = [
        {"k": "boot", "epoch": 1},
        {"k": "lease", **lease_record(l1)},
        {"k": "lease", **lease_record(l2)},
        {"k": "lease_end", **lease_end_record(l2.parent_chunk_id)},
    ]
    state = replay(records)
    assert set(state.leases) == {100}
    assert Lease.from_record(state.leases[100]) == l1
    # double replay is a structural no-op, same as every other kind
    assert set(replay(records + records).leases) == {100}
    # a snapshot carries open leases across compaction
    state2 = replay(
        [{"k": "boot", "epoch": 1}, state.snapshot_obj()]
    )
    assert Lease.from_record(state2.leases[100]) == l1


def test_restarted_aggregator_drops_recovered_leases(tmp_path):
    wal = str(tmp_path / "agg.wal")

    async def scenario():
        journal, _ = Journal.open(wal)
        for pc in (100, 101):
            journal.append("lease", lease_record(Lease(
                parent_job_id=5, parent_chunk_id=pc,
                lower=0, upper=4095,
            )))
        await journal.flush()
        await journal.aclose()
        agg = await Aggregator.create(
            "a1", [("127.0.0.1", 1)], params=FAST, recover_from=wal,
        )
        # the open leases were dropped one-sidedly at boot: the parent
        # already requeued those ranges, possibly to a sibling
        assert agg.stats["leases_dropped"] == 2
        assert not agg.inner.recovered_leases
        await agg.close()
        state = replay_wal(wal)
        assert not state.leases

    def replay_wal(path):
        from tpuminter.journal import scan
        with open(path, "rb") as fh:
            records, _clean = scan(fh.read())
        return replay(records)

    run(scenario())


# ---------------------------------------------------------------------------
# the two-tier drills (the tier-1 federation gate)
# ---------------------------------------------------------------------------

async def _fleet(port, n=2, batch=64):
    return [
        asyncio.ensure_future(run_miner(
            "127.0.0.1", port, CpuMiner(batch=batch), params=FAST,
            roll=True, beacon_interval=1e-6,
        ))
        for _ in range(n)
    ]


async def _teardown(miners=(), serves=(), nodes=()):
    for t in list(miners) + list(serves):
        t.cancel()
    await asyncio.gather(*miners, *serves, return_exceptions=True)
    for node in nodes:
        try:
            await node.close()
        except Exception:
            pass


def test_two_tier_rolled_target_end_to_end():
    """Client → parent → aggregator → fleet: the exact brute-forced
    minimum comes back through both tiers, every index is counted at
    the parent exactly once, and the parent's control traffic is the
    MERGED beacon stream (at most one per lease per tick), not the
    fleet's."""
    ens = 8
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=1)

    async def scenario():
        parent = await Coordinator.create(params=FAST, roll_budget=4)
        pserve = asyncio.ensure_future(parent.serve())
        agg = await Aggregator.create(
            "a1", [("127.0.0.1", parent.port)], params=FAST,
            beacon_interval=0.05, roll_budget=2,
        )
        aserve = asyncio.ensure_future(agg.serve())
        miners = await _fleet(agg.port)
        try:
            res = await asyncio.wait_for(
                submit("127.0.0.1", parent.port, req, params=FAST), 60.0
            )
            assert not res.found
            assert (res.hash_value, res.nonce) == (h_min, g_min)
            assert parent.stats["hashes"] == ens << NB
            assert parent.stats["leases_delegated"] > 0
            assert agg.stats["leases_taken"] > 0
            assert agg.stats["results_up"] > 0
            # fan-in flattening: the parent accepted (far) fewer
            # beacons than the inner tier absorbed from the fleet
            inner_beacons = agg.inner.stats["beacons_accepted"]
            if inner_beacons:
                assert (
                    parent.stats["beacons_accepted"] <= inner_beacons
                )
        finally:
            await _teardown(miners, [aserve, pserve], [agg, parent])

    run(scenario())


def test_parent_control_per_segment_stays_flat_as_the_fleet_grows():
    """The fan-in flattening, measured: the control messages the parent
    accepts (beacons + results) per settled segment with four miners
    behind one aggregator stay within 2x of the one-miner figure — the
    aggregator merges its fleet's beacons into one stream per lease."""
    ens = 8
    req = _rolled_request(ens, target=1)

    async def per_segment(n):
        parent = await Coordinator.create(params=FAST, roll_budget=4)
        pserve = asyncio.ensure_future(parent.serve())
        agg = await Aggregator.create(
            "a1", [("127.0.0.1", parent.port)], params=FAST,
            beacon_interval=0.05, roll_budget=2,
        )
        aserve = asyncio.ensure_future(agg.serve())
        miners = await _fleet(agg.port, n=n)
        try:
            res = await asyncio.wait_for(
                submit("127.0.0.1", parent.port, req, params=FAST), 60.0
            )
            assert not res.found
            assert parent.stats["hashes"] == ens << NB
            up = (parent.stats["beacons_accepted"]
                  + parent.stats["results_accepted"])
            return up / ens
        finally:
            await _teardown(miners, [aserve, pserve], [agg, parent])

    one = run(per_segment(1))
    four = run(per_segment(4))
    assert 0 < four <= 2 * one, (one, four)


def test_aggregator_crash_mid_lease_is_exactly_once(tmp_path):
    """Kill the aggregator mid-lease (journal crashed, no goodbye),
    restart it over the same WAL with a fresh fleet: the parent
    requeues the dead tier's dispatches, the restarted node drops any
    replayed open lease, and the job still settles to the exact
    minimum with every index counted at the parent exactly once."""
    ens = 8
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=1)
    wal = str(tmp_path / "agg.wal")

    async def scenario():
        parent = await Coordinator.create(params=FAST, roll_budget=2)
        pserve = asyncio.ensure_future(parent.serve())
        agg1 = await Aggregator.create(
            "a1", [("127.0.0.1", parent.port)], params=FAST,
            recover_from=wal, beacon_interval=0.05, roll_budget=1,
        )
        aserve1 = asyncio.ensure_future(agg1.serve())
        miners1 = await _fleet(agg1.port)
        submit_task = asyncio.ensure_future(submit(
            "127.0.0.1", parent.port, req, params=FAST
        ))
        agg2 = None
        aserve2 = None
        miners2 = []
        try:
            t0 = time.monotonic()
            while agg1.stats["leases_taken"] < 1:
                assert time.monotonic() - t0 < 30, "no lease ever taken"
                await asyncio.sleep(0.005)
            # -- kill -9 mid-lease -----------------------------------
            agg1.crash()
            for t in miners1:
                t.cancel()
            await asyncio.gather(*miners1, return_exceptions=True)
            aserve1.cancel()
            await asyncio.gather(aserve1, return_exceptions=True)
            # -- restart over the same journal -----------------------
            agg2 = await Aggregator.create(
                "a1", [("127.0.0.1", parent.port)], params=FAST,
                recover_from=wal, beacon_interval=0.05, roll_budget=1,
            )
            aserve2 = asyncio.ensure_future(agg2.serve())
            miners2 = await _fleet(agg2.port)
            res = await asyncio.wait_for(submit_task, 60.0)
            submit_task = None
            assert not res.found
            assert (res.hash_value, res.nonce) == (h_min, g_min)
            # the parent's ledger: every index settled exactly once —
            # beaconed prefixes kept, the requeued remainder re-mined
            # by the restarted tier, nothing double-counted
            assert parent.stats["hashes"] == ens << NB
        finally:
            if submit_task is not None:
                submit_task.cancel()
                await asyncio.gather(submit_task, return_exceptions=True)
            serves = [s for s in (aserve2, pserve) if s is not None]
            nodes = [n for n in (agg2, parent) if n is not None]
            await _teardown(miners2, serves, nodes)

    run(scenario())


def test_sibling_steals_the_unbeaconed_suffix():
    """Two sibling aggregators under one parent: one's fleet never
    progresses, the other drains early and Steals. The parent
    re-leases the stalled assignment's un-beaconed suffix under a
    bumped lease epoch; the thief mines it and the job settles to the
    exact minimum with no index double-counted."""
    ens = 8
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=1)

    async def scenario():
        parent = await Coordinator.create(
            params=FAST, roll_budget=4, pipeline_depth=1,
            steal_after=0.1,
        )
        pserve = asyncio.ensure_future(parent.serve())
        # the straggler: a tier with NO fleet — its lease never moves
        slow = await Aggregator.create(
            "slow", [("127.0.0.1", parent.port)], params=FAST,
            beacon_interval=0.05, roll_budget=1,
        )
        sserve = asyncio.ensure_future(slow.serve())
        fast = await Aggregator.create(
            "fast", [("127.0.0.1", parent.port)], params=FAST,
            beacon_interval=0.05, steal_interval=0.15, roll_budget=1,
        )
        fserve = asyncio.ensure_future(fast.serve())
        miners = await _fleet(fast.port)
        try:
            t0 = time.monotonic()
            while len(parent._miners) < 2:
                assert time.monotonic() - t0 < 30
                await asyncio.sleep(0.005)
            res = await asyncio.wait_for(
                submit("127.0.0.1", parent.port, req, params=FAST), 60.0
            )
            assert not res.found
            assert (res.hash_value, res.nonce) == (h_min, g_min)
            assert parent.stats["chunks_stolen"] >= 1
            assert fast.stats["steals_sent"] >= 1
            # exactly-once across the steal: the stolen suffix settled
            # through the thief only
            assert parent.stats["hashes"] == ens << NB
        finally:
            await _teardown(
                miners, [fserve, sserve, pserve], [fast, slow, parent]
            )

    run(scenario())


def test_parent_failover_to_promoted_standby(tmp_path):
    """Kill the parent machine mid-lease: the WAL-shipped standby
    promotes with a fenced epoch, the aggregator's upward rotation
    lands on it, the durable client re-submits and rebinds, and the
    answer is still the exact two-tier minimum."""
    ens = 8
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=1, client_key="t:fed")
    pwal = str(tmp_path / "parent.wal")
    swal = str(tmp_path / "standby.wal")

    async def resilient_submit(ports):
        while True:
            for port in ports:
                try:
                    return await submit(
                        "127.0.0.1", port, req, params=FAST,
                    )
                except (LspConnectError, LspConnectionLost, JobRefused):
                    await asyncio.sleep(0.05)

    async def scenario():
        from tpuminter.replication import ReplicationStandby

        standby = await ReplicationStandby.create(swal, params=FAST)
        standby_task = asyncio.ensure_future(standby.run())
        parent = await Coordinator.create(
            params=FAST, roll_budget=2, recover_from=pwal,
            replicate_to=[("127.0.0.1", standby.port)],
        )
        pserve = asyncio.ensure_future(parent.serve())
        agg = await Aggregator.create(
            "a1",
            [("127.0.0.1", parent.port), ("127.0.0.1", standby.port)],
            params=FAST, beacon_interval=0.05, roll_budget=1,
        )
        aserve = asyncio.ensure_future(agg.serve())
        miners = await _fleet(agg.port)
        client = asyncio.ensure_future(
            resilient_submit([parent.port, standby.port])
        )
        promoted = None
        promoted_serve = None
        try:
            t0 = time.monotonic()
            while parent.stats["leases_delegated"] < 1:
                assert time.monotonic() - t0 < 30, "no lease delegated"
                await asyncio.sleep(0.005)
            # -- the parent machine dies -----------------------------
            parent.crash()
            await asyncio.wait_for(standby.primary_lost.wait(), 15.0)
            promoted = await standby.promote(roll_budget=2)
            promoted_serve = asyncio.ensure_future(promoted.serve())
            res = await asyncio.wait_for(client, 60.0)
            client = None
            assert not res.found
            assert (res.hash_value, res.nonce) == (h_min, g_min)
            # the promoted parent served the surviving tier: the
            # aggregator rotated to it and leased from it
            assert promoted.stats["leases_delegated"] >= 1
        finally:
            if client is not None:
                client.cancel()
                await asyncio.gather(client, return_exceptions=True)
            pserve.cancel()
            standby_task.cancel()
            serves = [s for s in (promoted_serve,) if s is not None]
            await asyncio.gather(
                pserve, standby_task, return_exceptions=True
            )
            nodes = [agg] + ([promoted] if promoted is not None else [])
            await _teardown(miners, [aserve] + serves, nodes)

    run(scenario())


# ---------------------------------------------------------------------------
# folds satellite: two-level tree_merge == flat fold
# ---------------------------------------------------------------------------

def _fold_cases():
    return [
        wfolds.FMin(),
        wfolds.TopK(4),
        wfolds.FirstMatch(threshold=1 << 18),
        wfolds.FSum(),
    ]


def _chunk_partials(fold, rng, n_chunks=12, width=16):
    """Per-chunk accumulators over a deterministic value landscape,
    keyed by chunk index (the dedup key a coverage gate uses)."""
    partials = {}
    for c in range(n_chunks):
        values = [rng.randrange(1 << 22) for _ in range(width)]
        partials[c] = fold.of_batch(c * width, values)
    return partials


def _flat(fold, parts):
    acc = fold.initial()
    for p in parts:
        acc = fold.combine(acc, p)
    return acc


def test_two_level_merge_equals_flat_fold_for_every_discipline():
    rng = random.Random(18)
    for fold in _fold_cases():
        partials = _chunk_partials(fold, rng)
        chunks = list(partials)
        for _trial in range(20):
            rng.shuffle(chunks)
            # random partition into aggregator-sized groups
            groups, i = [], 0
            while i < len(chunks):
                step = rng.randrange(1, 5)
                groups.append(
                    [partials[c] for c in chunks[i:i + step]]
                )
                i += step
            assert wfolds.tree_merge(fold, groups) == _flat(
                fold, [partials[c] for c in sorted(partials)]
            ), fold.name


def test_duplicate_delivery_and_replay_are_harmless_when_gated():
    """Idempotent folds absorb duplicates structurally; the sum fold
    (and fmatch's probe count) rely on the coverage gate instead —
    modeled here as per-chunk dedup at EACH tier, which is exactly
    what the journal plane's interval subtraction provides. Composed
    tiers therefore stay exactly-once without any cross-tier
    bookkeeping."""
    rng = random.Random(19)
    for fold in _fold_cases():
        partials = _chunk_partials(fold, rng)
        want = _flat(fold, [partials[c] for c in sorted(partials)])
        chunks = list(partials) + list(partials)[:5]  # duplicates
        rng.shuffle(chunks)
        if fold.idempotent and fold.name != "fmatch":
            # duplicates may flow straight into the fold
            groups = [
                [partials[c] for c in chunks[:7]],
                [partials[c] for c in chunks[7:]],
            ]
            # replay: the whole second group delivered twice
            groups.append(groups[1])
            assert wfolds.tree_merge(fold, groups) == want, fold.name
        # with the per-tier gate (dedup by chunk id at each level),
        # EVERY fold — including non-idempotent sum — composes
        seen_l1, seen_l2 = set(), set()
        groups = [[], []]
        for j, c in enumerate(chunks):
            tier = j % 2
            seen = seen_l1 if tier == 0 else seen_l2
            if c in seen:
                continue  # the gate: a range absorbs once per tier
            seen.add(c)
            groups[tier].append(partials[c])
        if seen_l1 & seen_l2:
            # cross-group duplicates must be gated at the TOP tier
            # too; model the parent's gate by removing them
            dup = seen_l1 & seen_l2
            groups[1] = [
                partials[c] for c in sorted(seen_l2 - dup)
            ]
        assert wfolds.tree_merge(fold, groups) == want, fold.name


def test_partial_coverage_beacons_compose():
    """A tier reporting only a prefix of its chunks (the merged-beacon
    shape) still composes: the two-level merge over any reported
    subset equals the flat fold over that subset, for every fold."""
    rng = random.Random(20)
    for fold in _fold_cases():
        partials = _chunk_partials(fold, rng)
        for _trial in range(10):
            reported = sorted(
                c for c in partials if rng.random() < 0.6
            )
            cut = rng.randrange(len(reported) + 1)
            groups = [
                [partials[c] for c in reported[:cut]],
                [partials[c] for c in reported[cut:]],
            ]
            assert wfolds.tree_merge(fold, groups) == _flat(
                fold, [partials[c] for c in reported]
            ), fold.name


# ---------------------------------------------------------------------------
# transport satellite: slow-loris deadlines at the ConnState layer
# ---------------------------------------------------------------------------

def _conn(**params):
    delivered, lost = [], []
    conn = ConnState(
        1, Params(**params), lambda f: None, delivered.append,
        lost.append,
    )
    return conn, delivered, lost


def test_drip_feeder_hits_the_total_time_read_deadline():
    """One more-fragments frame per epoch: byte progress EVERY epoch,
    so the silent-epoch liveness never fires — only the total-time
    read deadline bounds it."""
    conn, delivered, lost = _conn(read_deadline_epochs=6)
    seq = 1
    for _epoch in range(10):
        conn.on_frame(Frame(MsgType.DATA, 1, seq, bytes(_MORE) + b"z"))
        seq += 1
        conn.on_epoch()
        if conn.lost:
            break
    assert conn.lost and lost
    assert "mid-reassembly" in lost[0]
    assert not delivered


def test_completed_messages_reset_the_reassembly_clock():
    conn, delivered, _lost = _conn(read_deadline_epochs=4)
    seq = 1
    for _round in range(5):
        # two fragments, two epochs apart: finishes inside the bound
        conn.on_frame(Frame(MsgType.DATA, 1, seq, bytes(_MORE) + b"a"))
        seq += 1
        conn.on_epoch()
        conn.on_frame(Frame(MsgType.DATA, 1, seq, b"\x00" + b"b"))
        seq += 1
        conn.on_epoch()
    assert not conn.lost
    assert len(delivered) == 5


def test_mute_peer_hits_the_first_message_deadline():
    conn, _delivered, lost = _conn(read_deadline_epochs=3)
    conn.first_msg_deadline_epochs = 3
    for _epoch in range(5):
        # heartbeats flow: liveness is satisfied, only the first-app-
        # message deadline can fire
        conn._received_this_epoch = True
        conn.on_epoch()
        if conn.lost:
            break
    assert conn.lost and lost
    assert "no application message" in lost[0]


def test_deadlines_default_off_and_honest_peers_unaffected():
    conn, delivered, _lost = _conn()
    assert conn.params.read_deadline_epochs == 0
    conn.on_frame(Frame(MsgType.DATA, 1, 1, b"\x00hello"))
    for _epoch in range(4):
        conn._received_this_epoch = True
        conn.on_epoch()
    assert not conn.lost
    assert len(delivered) == 1
    with pytest.raises(ValueError):
        Params(read_deadline_epochs=-1)


# ---------------------------------------------------------------------------
# scale satellite: durable ckeys through the bounded tables
# ---------------------------------------------------------------------------

def _scale_probe(n_keys):
    # winner/dedup table: n_keys distinct durable identities replayed
    # through the journal fold stay inside winners_cap, newest kept
    records = [{"k": "boot", "epoch": 1}]
    for i in range(n_keys):
        records.append({
            "k": "finish", "id": i + 1, "ckey": f"scale:{i}", "cjid": 1,
            "mode": PowMode.MIN.value, "n": i, "h": "ff", "found": False,
            "s": 1, "ts": 0.0,
        })
    cap = 2048
    state = replay(records, winners_cap=cap)
    assert len(state.winners) == cap
    assert (f"scale:{n_keys - 1}", 1) in state.winners
    assert (f"scale:{n_keys - cap - 1}", 1) not in state.winners
    assert not state.jobs  # every finish retired its job

    async def quota():
        coord = await Coordinator.create(
            params=FAST, quota_rate=5.0, quota_burst=2.0,
        )
        req = _rolled_request(1, target=1)
        admitted = 0
        for i in range(n_keys):
            msg = dataclasses.replace(req, client_key=f"scale:{i}")
            if coord._admit(i, msg) == 0:
                admitted += 1
        # every identity got its burst admission; the bucket table
        # LRU-shed down to its cap instead of holding n_keys entries
        assert admitted == n_keys
        assert len(coord._buckets) <= QUOTA_BUCKETS_CAP
        await coord.close()

    run(quota())


def test_scale_probe_20k_durable_ckeys():
    _scale_probe(20_000)


@pytest.mark.slow
def test_scale_probe_100k_durable_ckeys():
    _scale_probe(100_000)


# ---------------------------------------------------------------------------
# WAL-bound satellite: live compaction keeps the file bounded
# ---------------------------------------------------------------------------

def test_writer_wal_stays_bounded_under_sustained_load(tmp_path):
    """Soak shape: many short-lived jobs through a writer-mode journal
    with a small compaction threshold — the live state stays tiny, so
    automatic compaction must keep the FILE bounded (threshold plus
    one snapshot plus the batch in flight), not merely growing slower."""
    path = str(tmp_path / "soak.wal")

    async def scenario():
        from tests.test_replication import _req_obj

        journal, state = Journal.open(path, compact_bytes=32 * 1024)
        # owner's contract: compaction needs a snapshot of live state —
        # fold the same records into a shadow and hand it over, exactly
        # as the coordinator's snapshot_provider does
        journal.snapshot_provider = state.snapshot_obj

        def log(kind, obj):
            journal.append(kind, obj)
            state.apply({**obj, "k": kind})

        peak = 0
        for jid in range(1, 2001):
            log("job", {"id": jid, "req": _req_obj(jid)})
            log("finish", {
                "id": jid, "ckey": "", "cjid": 0,
                "mode": PowMode.MIN.value, "n": 0, "h": "ff",
                "found": False, "s": 1, "ts": 0.0,
            })
            if jid % 100 == 0:
                await journal.flush()
                peak = max(peak, os.path.getsize(path))
        await journal.flush()
        peak = max(peak, os.path.getsize(path))
        await journal.aclose()
        assert journal.stats["compactions"] >= 1
        # bound: threshold + one snapshot of (tiny) live state + slack
        # for the record batch in flight when the threshold tripped
        assert peak < 3 * 32 * 1024, peak
        # and the surviving file still replays to the right state
        _journal2, state = Journal.open(path)
        assert not state.jobs

    run(scenario())
