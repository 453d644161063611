"""Roll-budget chunking tests (ISSUE 14): dispatch rolled work in
extranonce units with sub-chunk progress beacons.

- **Arithmetic mirrors** (deterministic versions of the hypothesis
  properties in tests/test_properties.py, since this image lacks
  hypothesis): ``chain.roll_span`` must expand to exactly ``count``
  whole segments — the coordinator's carve and the worker's expansion
  agree bit-for-bit — and beacon-style PARTIAL settles must replay
  through the journal exactly like interval subtraction.
- **End-to-end**: a roll-capable fleet against a budgeted coordinator
  mines a shrunken rolled job via RollAssign dispatch (counted, not
  assumed), emits accepted Beacons, and still lands the bit-exact
  min-fold and hash accounting. Both no-flag-day directions are
  pinned like the PR 4 codec negotiation tests: an old (``roll=False``)
  worker gets classic Assigns from a budgeted coordinator, and a roll
  worker gets classic Assigns from a budget-0 coordinator — exact
  results either way, zero RollAssigns/Beacons on the wire.
- **Crash drill**: kill -9 the journaled coordinator after >= 2
  accepted beacons mid-chunk; the journal replays the beacon settles
  as ordinary 0xB7 records, the recovered job re-mines ONLY the
  un-settled suffix, and the resumed fleet still lands the exact min.
- **Control-plane collapse**: ``scripts/loadgen.py``'s paired rolled
  A/B (budgeted RollAssigns against global-index chunks, same fleet
  and clients) passes its own ``rolled_check`` gates at both segment
  sizes, 2^20 and the production 2^32.
"""

import asyncio
import os
import random
import struct
import sys
import time

import pytest

from tpuminter import chain
from tpuminter.client import submit
from tpuminter.coordinator import Coordinator
from tpuminter.journal import encode_record, merge_ranges, replay, scan
from tpuminter.protocol import PowMode, Request, request_to_obj
from tpuminter.worker import CpuMiner, run_miner, run_miner_reconnect

from tests.test_e2e import FAST, run
from tests.test_extranonce import fixture

sys.path.insert(
    0,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ),
)

import loadgen  # noqa: E402  (scripts/ is not a package)

NB = 10  # nonce_bits under test (shrunken so a CI sweep rolls)


def _brute(prefix, suffix, branch, hdr80, ens):
    """(hash, global index) minimum over ``ens`` extranonce segments."""
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    best = None
    for en in range(ens):
        p76 = chain.rolled_header(hdr80, cb, branch, en).pack()[:76]
        for n in range(1 << NB):
            h = chain.hash_to_int(chain.dsha256(p76 + struct.pack("<I", n)))
            cand = (h, (en << NB) | n)
            if best is None or cand < best:
                best = cand
    return best


def _rolled_request(ens, *, target, job_id=1, client_key=""):
    prefix, suffix, branch, hdr80 = fixture()
    return Request(
        job_id=job_id, mode=PowMode.TARGET, lower=0,
        upper=(ens << NB) - 1, header=hdr80, target=target,
        coinbase_prefix=prefix, coinbase_suffix=suffix,
        extranonce_size=4, branch=tuple(branch), nonce_bits=NB,
        client_key=client_key,
    )


# ---------------------------------------------------------------------------
# arithmetic mirrors
# ---------------------------------------------------------------------------

def test_roll_span_matches_segment_expansion():
    """roll_span(e0, count) is exactly count WHOLE segments: aligned at
    both ends and tiled by rolled_segments with full nonce sweeps —
    the one expansion the carve and the worker must share."""
    rng = random.Random(0xB9)
    cases = [(1, 0, 1), (1, 5, 3), (10, 2, 4), (32, 0, 1),
             (32, 0xFFFFFFFF, 1)]
    cases += [
        (rng.choice([2, 7, 10, 20, 32]), rng.randrange(1 << 16),
         rng.randrange(1, 64))
        for _ in range(50)
    ]
    for nb, e0, count in cases:
        lower, upper = chain.roll_span(e0, count, nb)
        mask = (1 << nb) - 1
        assert lower == e0 << nb
        assert upper - lower + 1 == count << nb
        segs = list(chain.rolled_segments(lower, upper, nb))
        assert len(segs) == count
        assert [en for en, _, _, _ in segs] == list(range(e0, e0 + count))
        assert all(n_lo == 0 and n_hi == mask for _, _, n_lo, n_hi in segs)


def test_roll_span_rejects_empty_count():
    import pytest

    with pytest.raises(ValueError):
        chain.roll_span(3, 0, 10)


def test_beacon_partial_settles_replay_like_subtraction():
    """A journal mixing beacon-style PARTIAL settles (a prefix of an
    in-flight chunk) with whole-chunk settles replays to exactly the
    set-model's un-settled ranges — the zero-format-change property
    recovery leans on: a beacon settle IS an ordinary settle record
    over a sub-range."""
    rng = random.Random(14)
    for _ in range(30):
        segs = rng.randrange(1, 9)
        total = segs << NB
        req = _rolled_request(segs, target=1)
        covered = set()
        blob = encode_record(
            {"k": "job", "id": 1, "req": request_to_obj(req)}
        )
        # random chunk grid; each chunk gets 0..2 monotone beacon
        # prefixes and then maybe its final whole-range settle
        cuts = sorted(rng.sample(range(1, total), min(5, total - 1)))
        chunks = list(zip([0] + cuts, [c - 1 for c in cuts] + [total - 1]))
        for lo, hi in chunks:
            hw = lo - 1
            for _ in range(rng.randrange(3)):
                if hw >= hi - 1:
                    break
                hw = rng.randrange(hw + 1, hi)
                blob += encode_record({
                    "k": "settle", "id": 1, "lo": lo, "hi": hw,
                    "n": lo, "s": hw - lo + 1, "h": "ff",
                })
                covered.update(range(lo, hw + 1))
                lo = hw + 1  # the live chunk advances past the beacon
            if rng.random() < 0.6 and lo <= hi:
                blob += encode_record({
                    "k": "settle", "id": 1, "lo": lo, "hi": hi,
                    "n": lo, "s": hi - lo + 1, "h": "ff",
                })
                covered.update(range(lo, hi + 1))
        recs, _ = scan(blob)
        state = replay(recs)
        want = []
        g = 0
        while g < total:
            if g in covered:
                g += 1
                continue
            start = g
            while g < total and g not in covered:
                g += 1
            want.append((start, g - 1))
        assert merge_ranges(state.jobs[1].remaining) == want
        assert state.jobs[1].hashes_done == len(covered)


# ---------------------------------------------------------------------------
# end-to-end: the dialect engages, and both interop directions hold
# ---------------------------------------------------------------------------

async def _rolled_cluster_run(req, *, roll_budget, worker_roll,
                              beacon_interval=1e-6, batch=16,
                              chunk_size=100_000, n_miners=1):
    """One rolled job through a real coordinator + run_miner fleet with
    the given dialect knobs; returns (final Result, coordinator stats,
    dispatched-chunk count)."""
    coord = await Coordinator.create(
        params=FAST, chunk_size=chunk_size, roll_budget=roll_budget,
    )
    serve = asyncio.ensure_future(coord.serve())
    miners = [
        asyncio.ensure_future(run_miner(
            "127.0.0.1", coord.port, CpuMiner(batch=batch), params=FAST,
            roll=worker_roll, beacon_interval=beacon_interval,
        ))
        for _ in range(n_miners)
    ]
    try:
        await asyncio.sleep(0.1)
        res = await asyncio.wait_for(
            submit("127.0.0.1", coord.port, req, params=FAST), 60.0
        )
        return res, dict(coord.stats), coord._next_chunk_id - 1
    finally:
        for t in miners:
            t.cancel()
        await asyncio.gather(*miners, return_exceptions=True)
        serve.cancel()
        await asyncio.gather(serve, return_exceptions=True)
        await coord.close()


def test_rolled_e2e_budget_engages_beacons_and_exact_min():
    """The positive direction: budgeted coordinator + roll worker. The
    job is dispatched as RollAssigns (counted), sub-chunk progress
    flows back as accepted Beacons, and the exhaustion answer is still
    the bit-exact min with bit-exact hash accounting — beacon settles
    and final Results never double-count."""
    ens = 8
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=1)  # unbeatable: exhaust + min

    async def scenario():
        return await _rolled_cluster_run(
            req, roll_budget=8, worker_roll=True,
        )

    res, stats, chunks = run(scenario())
    assert not res.found
    assert (res.hash_value, res.nonce) == (h_min, g_min)
    assert stats["chunks_roll_dispatched"] > 0
    assert stats["chunks_roll_dispatched"] == chunks  # no classic mix-in
    assert stats["beacons_accepted"] > 0
    assert stats["hashes"] == ens << NB  # exact: no double-count
    assert stats["results_rejected"] == 0


def test_rolled_e2e_budget_finds_winner():
    """Same stack, beatable target: the winner Result (not a beacon)
    finishes the job, exactly like classic dispatch."""
    ens = 4
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=h_min)

    async def scenario():
        return await _rolled_cluster_run(
            req, roll_budget=4, worker_roll=True,
        )

    res, stats, _ = run(scenario())
    assert res.found
    assert (res.nonce, res.hash_value) == (g_min, h_min)
    assert stats["chunks_roll_dispatched"] > 0


def test_rolled_e2e_old_worker_gets_classic_assigns():
    """No-flag-day, worker side: a pre-dialect worker (roll=False —
    its Join never advertises) against a BUDGETED coordinator must see
    only classic Assigns and still land the exact answer."""
    ens = 4
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=1)

    async def scenario():
        return await _rolled_cluster_run(
            req, roll_budget=8, worker_roll=False, chunk_size=1024,
        )

    res, stats, _ = run(scenario())
    assert not res.found
    assert (res.hash_value, res.nonce) == (h_min, g_min)
    assert stats["chunks_roll_dispatched"] == 0
    assert stats["beacons_accepted"] == 0
    assert stats["hashes"] == ens << NB


def test_rolled_e2e_budget_zero_is_the_old_coordinator():
    """No-flag-day, coordinator side: a roll-capable worker against a
    budget-0 coordinator (the shipping default) sees only classic
    Assigns, emits zero beacons, and lands the exact answer — every
    pre-dialect deployment keeps behaving bit-for-bit."""
    ens = 4
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=1)

    async def scenario():
        return await _rolled_cluster_run(
            req, roll_budget=0, worker_roll=True, chunk_size=1024,
        )

    res, stats, _ = run(scenario())
    assert not res.found
    assert (res.hash_value, res.nonce) == (h_min, g_min)
    assert stats["chunks_roll_dispatched"] == 0
    assert stats["beacons_accepted"] == 0
    assert stats["hashes"] == ens << NB


# ---------------------------------------------------------------------------
# crash drill: beacons bound the re-mine
# ---------------------------------------------------------------------------

class _SlowRollMiner(CpuMiner):
    """CpuMiner that naps per batch so a CI-sized rolled chunk stays
    mid-flight long enough to beacon at least twice before the kill."""

    def __init__(self, batch=16, nap=0.002):
        super().__init__(batch=batch)
        self._nap = nap

    def mine(self, request):
        for item in super().mine(request):
            time.sleep(self._nap)
            yield item


def test_crash_mid_roll_chunk_replays_only_unsettled(tmp_path):
    """Kill -9 the journaled coordinator after >= 2 accepted beacons on
    an in-flight roll-budget chunk. The journal (unchanged 0xB7 settle
    records) must replay the beaconed prefix as SETTLED — the recovered
    job re-mines only the un-settled suffix — and the resumed fleet
    still lands the bit-exact min with exactly-once accounting."""
    wal = str(tmp_path / "roll.wal")
    ens = 8
    total = ens << NB
    prefix, suffix, branch, hdr80 = fixture()
    h_min, g_min = _brute(prefix, suffix, branch, hdr80, ens)
    req = _rolled_request(ens, target=1, client_key="roll-crash")

    async def scenario():
        coord = await Coordinator.create(
            params=FAST, chunk_size=100_000, roll_budget=8,
            recover_from=wal,
        )
        port = coord.port
        serve = asyncio.ensure_future(coord.serve())
        miner = asyncio.ensure_future(run_miner_reconnect(
            "127.0.0.1", port, _SlowRollMiner(), params=FAST,
            base_backoff=0.05, max_backoff=0.4, beacon_interval=1e-6,
        ))
        sub = asyncio.ensure_future(submit(
            "127.0.0.1", port, req, params=FAST,
            client_key="roll-crash", reconnect=True, base_backoff=0.05,
        ))
        coord2 = None
        try:
            t0 = time.monotonic()
            while coord.stats["beacons_accepted"] < 2:
                assert time.monotonic() - t0 < 30, "no beacons pre-crash"
                await asyncio.sleep(0.01)
            assert coord.stats["jobs_done"] == 0, (
                "crash must land mid-job; slow the miner down"
            )
            assert coord.stats["chunks_roll_dispatched"] > 0
            # the tick flush is a normal runtime event — run one so the
            # drill's replay assertions are deterministic (a settle
            # still buffered at the instant of death just re-mines)
            await coord._journal.flush()
            # -- kill -9 -------------------------------------------------
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            endpoint = coord.server.endpoint
            coord.crash()
            await endpoint.wait_closed()
            # -- the journal alone bounds the re-mine --------------------
            with open(wal, "rb") as fh:
                recs, _ = scan(fh.read())
            state = replay(recs)
            job = state.jobs[req.job_id]
            settled = job.hashes_done
            assert 0 < settled < total
            remaining = merge_ranges(job.remaining)
            assert sum(hi - lo + 1 for lo, hi in remaining) == (
                total - settled
            )
            # beacons settle chunk PREFIXES from index 0, so recovery
            # re-mines a pure suffix of the space
            assert remaining[0][0] == settled
            # -- restart on the same port; the fleet resumes -------------
            for attempt in range(100):
                try:
                    coord2 = await Coordinator.create(
                        port, params=FAST, chunk_size=100_000,
                        roll_budget=8, recover_from=wal,
                    )
                    break
                except OSError:
                    await asyncio.sleep(0.02)
            assert coord2 is not None, "could not rebind the port"
            serve2 = asyncio.ensure_future(coord2.serve())
            try:
                res = await asyncio.wait_for(sub, 60.0)
                assert not res.found
                assert (res.hash_value, res.nonce) == (h_min, g_min)
                # the recovered coordinator mined ONLY the un-settled
                # suffix: its own hash ledger is the complement of the
                # replayed prefix
                assert coord2.stats["hashes"] == total - settled
                assert coord2.stats["results_rejected"] == 0
            finally:
                serve2.cancel()
                await asyncio.gather(serve2, return_exceptions=True)
        finally:
            miner.cancel()
            sub.cancel()
            await asyncio.gather(miner, sub, return_exceptions=True)
            if coord2 is not None:
                await coord2.close()
            await coord.close()

    run(scenario(), timeout=120.0)


@pytest.mark.parametrize("nonce_bits", [20, 32])
def test_roll_budget_collapses_control_traffic_per_segment(nonce_bits):
    """Budgeted RollAssign dispatch sends fewer control messages and
    fewer wire bytes per settled 2^nonce_bits segment than global-index
    chunking, by at least ``rolled_check``'s floor (1000x at the
    production 2^32, 100x at 2^20), with every beacon accepted, beacons
    at most 5% of results, and no miner lost or result rejected."""
    m = asyncio.run(loadgen.run_rolled(8, 2, 1.0, nonce_bits=nonce_bits))
    assert loadgen.rolled_check(m) == []
    roll, classic = m["roll"], m["classic"]
    assert roll["ctrl_msgs_per_segment"] < classic["ctrl_msgs_per_segment"]
    assert roll["wire_bytes_per_segment"] < classic["wire_bytes_per_segment"]
    if nonce_bits == 32:
        assert m["collapse_ratio_msgs"] >= 1000.0
