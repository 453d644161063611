"""Durability + crash-recovery tests (ISSUE 3's fault-injection layer).

Three strata, mirroring how the LSP stack is tested:

- **Pure journal properties** (deterministic seeded drives, the
  bundled-codec corruption properties of tests/test_properties.py
  applied to the on-disk record stream): a torn/truncated tail
  truncates cleanly, a corrupted record can only look like loss of a
  suffix (never like different records), and replay is idempotent
  (double replay, and snapshot-compaction equivalence).
- **Journal runtime**: append/flush/reopen round-trips state; ``kill
  -9`` via :meth:`Journal.crash` loses at most the unflushed tail.
- **Role e2e**: the LSP boot-epoch regression (a server restarted on
  the same port is a FRESH session — stale sequence state is never
  resumed), the coordinator crash drill (kill -9 mid-epoch with miners
  and ≥2 bound clients; restart from the journal; no acknowledged
  winner lost, exactly one answer per request, fleet resumes
  unattended), winner dedup across restarts, and the loadgen crash
  scenario's tier-1 gate.
"""

import asyncio
import json as _json
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

from tpuminter import chain  # noqa: E402
from tpuminter.client import submit  # noqa: E402
from tpuminter.coordinator import Coordinator  # noqa: E402
from tpuminter.journal import (  # noqa: E402
    Journal,
    encode_record,
    merge_ranges,
    replay,
    scan,
    subtract_range,
)
from tpuminter.lsp import (  # noqa: E402
    LspClient,
    LspConnectionLost,
    LspServer,
    Params,
)
from tpuminter.protocol import (  # noqa: E402
    PowMode,
    Request,
    request_to_obj,
)
from tpuminter.worker import CpuMiner, run_miner_reconnect  # noqa: E402

from tests.test_e2e import FAST, brute_min, run  # noqa: E402


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _mk_request(jid=1, upper=4095, ckey="", data=b"x"):
    return Request(
        job_id=jid, mode=PowMode.MIN, lower=0, upper=upper, data=data,
        client_key=ckey,
    )


def _record_stream(rng, n_jobs=4):
    """A plausible journal tail: jobs, interleaved settles, some
    finishes/abandons — seeded, so failures reproduce."""
    records = [{"k": "boot", "epoch": 1}]
    live = []
    for j in range(1, n_jobs + 1):
        upper = rng.randrange(1000, 5000)
        req = _mk_request(jid=j, upper=upper, ckey=f"c{j % 2}")
        records.append({"k": "job", "id": j, "req": request_to_obj(req)})
        live.append((j, upper))
    for _ in range(30):
        j, upper = rng.choice(live)
        lo = rng.randrange(0, upper)
        hi = min(upper, lo + rng.randrange(1, 512))
        records.append({
            "k": "settle", "id": j, "lo": lo, "hi": hi,
            "h": f"{rng.getrandbits(64):x}", "n": rng.randrange(lo, hi + 1),
            "s": hi - lo + 1,
        })
    j, _ = live[0]
    records.append({
        "k": "finish", "id": j, "ckey": "c1", "cjid": j, "mode": "min",
        "n": 7, "h": "ab", "found": True, "s": 100,
    })
    records.append({"k": "abandon", "id": live[1][0]})
    return records


def _state_key(state):
    """Canonical comparable view of a RecoveredState."""
    return {
        "epoch": state.boot_epoch,
        "next": state.next_job_id,
        "jobs": {
            jid: (tuple(j.remaining), j.best, j.hashes_done,
                  request_to_obj(j.request))
            for jid, j in state.jobs.items()
        },
        "winners": {k: dict(v) for k, v in state.winners.items()},
    }


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def test_subtract_and_merge_ranges():
    assert subtract_range([(0, 9)], 3, 5) == ([(0, 2), (6, 9)], 3)
    assert subtract_range([(0, 9)], 0, 9) == ([], 10)
    assert subtract_range([(0, 4)], 7, 9) == ([(0, 4)], 0)
    # idempotent: subtracting again removes nothing
    r, n = subtract_range([(0, 2), (6, 9)], 3, 5)
    assert (r, n) == ([(0, 2), (6, 9)], 0)
    assert merge_ranges([(5, 9), (0, 4), (20, 25)]) == [(0, 9), (20, 25)]


def test_subtract_range_randomized_against_set_model():
    rng = random.Random(7)
    for _ in range(200):
        universe = set()
        ranges = []
        cursor = 0
        for _ in range(rng.randrange(1, 5)):
            cursor += rng.randrange(1, 20)
            size = rng.randrange(1, 30)
            ranges.append((cursor, cursor + size - 1))
            universe |= set(range(cursor, cursor + size))
            cursor += size
        lo = rng.randrange(0, cursor + 10)
        hi = lo + rng.randrange(0, 40)
        new, removed = subtract_range(ranges, lo, hi)
        expect = universe - set(range(lo, hi + 1))
        got = set()
        for a, b in new:
            got |= set(range(a, b + 1))
        assert got == expect
        assert removed == len(universe) - len(expect)


# ---------------------------------------------------------------------------
# record codec: corruption can only look like loss of a suffix
# (the bundled-codec properties of test_properties.py, applied to disk)
# ---------------------------------------------------------------------------

def test_journal_records_roundtrip():
    rng = random.Random(1)
    records = _record_stream(rng)
    blob = b"".join(encode_record(r) for r in records)
    got, clean = scan(blob)
    assert got == records
    assert clean == len(blob)


def test_torn_tail_truncates_to_a_clean_prefix():
    """Truncation at EVERY byte boundary yields an exact prefix of the
    original records — a torn write can only lose a suffix."""
    rng = random.Random(2)
    records = _record_stream(rng, n_jobs=2)
    blob = b"".join(encode_record(r) for r in records)
    for keep in range(len(blob)):
        got, clean = scan(blob[:keep])
        assert got == records[: len(got)]
        assert len(got) < len(records)
        assert clean <= keep


def test_corrupted_record_loses_only_a_suffix():
    """A single-byte flip anywhere in the stream: whatever still
    decodes is an exact prefix of the original records — corruption is
    indistinguishable from a shorter journal, never a different one
    (CRC-32 over size‖payload per record)."""
    rng = random.Random(3)
    records = _record_stream(rng, n_jobs=2)
    blob = bytearray(b"".join(encode_record(r) for r in records))
    for _ in range(300):
        i = rng.randrange(len(blob))
        flip = rng.randrange(1, 256)
        blob[i] ^= flip
        got, _ = scan(bytes(blob))
        assert len(got) < len(records)
        assert got == records[: len(got)]
        blob[i] ^= flip  # restore for the next trial


def test_double_replay_is_idempotent():
    rng = random.Random(4)
    records = _record_stream(rng)
    once = replay(records)
    twice = replay(records + records)
    assert _state_key(once) == _state_key(twice)


def test_snapshot_compaction_is_replay_equivalent():
    """Replaying [boot, snapshot] (what compaction writes) plus a
    residual tail equals replaying the full original stream — and a
    duplicated tail after the snapshot (the records compaction may
    leave buffered) changes nothing."""
    rng = random.Random(5)
    records = _record_stream(rng)
    cut = len(records) - 6
    head, tail = records[:cut], records[cut:]
    state = replay(head)
    compacted = [{"k": "boot", "epoch": state.boot_epoch},
                 state.snapshot_obj()]
    assert _state_key(replay(records)) == _state_key(
        replay(compacted + tail)
    )
    # records already covered by the snapshot may ride after it too
    assert _state_key(replay(compacted + head[1:] + tail)) == _state_key(
        replay(records)
    )


def test_settle_replay_rebuilds_remaining_ranges_and_fold():
    req = _mk_request(jid=9, upper=999, ckey="k")
    records = [
        {"k": "boot", "epoch": 1},
        {"k": "job", "id": 1, "req": request_to_obj(req)},
        {"k": "settle", "id": 1, "lo": 0, "hi": 99, "h": "50", "n": 42,
         "s": 100},
        {"k": "settle", "id": 1, "lo": 300, "hi": 999, "h": "20", "n": 400,
         "s": 700},
    ]
    state = replay(records)
    job = state.jobs[1]
    assert job.remaining == [(100, 299)]
    assert job.best == (0x20, 400)
    assert job.hashes_done == 800
    # the finish retires the job and registers the winner for dedup
    records.append({
        "k": "finish", "id": 1, "ckey": "k", "cjid": 9, "mode": "min",
        "n": 400, "h": "20", "found": True, "s": 1000,
    })
    state = replay(records)
    assert not state.jobs
    assert state.winners[("k", 9)]["n"] == 400


# ---------------------------------------------------------------------------
# journal runtime: reopen, torn-tail repair, crash loses only the tail
# ---------------------------------------------------------------------------

def test_journal_reopen_replays_appends(tmp_path):
    path = str(tmp_path / "j.wal")

    async def session_one():
        journal, state = Journal.open(path)
        assert state.boot_epoch == 1
        req = _mk_request(jid=5, upper=100, ckey="me")
        journal.append("job", {"id": 1, "req": request_to_obj(req)})
        journal.append_encoded(
            b'{"id":1,"lo":0,"hi":49,"h":"aa","n":3,"s":50,"k":"settle"}'
        )
        fired = []
        journal.append(
            "finish",
            {"id": 2, "ckey": "me", "cjid": 6, "mode": "min", "n": 1,
             "h": "bb", "found": True, "s": 10},
            on_durable=lambda: fired.append(1),
        )
        await journal.flush()
        assert fired == [1]
        await journal.aclose()

    asyncio.run(session_one())
    journal2, state2 = Journal.open(path)
    assert state2.boot_epoch == 2  # monotone across incarnations
    assert state2.jobs[1].remaining == [(50, 100)]
    assert state2.winners[("me", 6)]["found"] is True

    # torn tail on disk: garbage after the valid prefix is repaired
    with open(path, "ab") as fh:
        fh.write(b"\xde\xad\xbe\xef-torn-write")
    journal3, state3 = Journal.open(path)
    assert state3.boot_epoch == 3
    assert state3.jobs[1].remaining == [(50, 100)]
    # the file is a clean record stream again (garbage truncated away,
    # then the new boot record appended)
    with open(path, "rb") as fh:
        data = fh.read()
    records, clean = scan(data)
    assert clean == len(data)
    assert records[-1] == {"k": "boot", "epoch": 3}


def test_journal_crash_loses_at_most_the_unflushed_tail(tmp_path):
    path = str(tmp_path / "j.wal")

    async def scenario():
        journal, _ = Journal.open(path)
        req = _mk_request(jid=1, upper=10)
        journal.append("job", {"id": 1, "req": request_to_obj(req)})
        await journal.flush()
        # buffered but never flushed: must vanish, not corrupt
        journal.append("abandon", {"id": 1})
        journal.crash()

    asyncio.run(scenario())
    _, state = Journal.open(path)
    assert 1 in state.jobs  # the flushed job survived; the tail is gone


def test_journal_disk_failure_fails_loudly_but_never_wedges_replies(
    tmp_path, monkeypatch
):
    """If the WAL's disk dies mid-flight (ENOSPC, yanked volume), the
    journal must stop journaling LOUDLY — but every on_durable callback
    (the thing that releases client replies) still fires, both for the
    batch that hit the error and for all later appends."""
    path = str(tmp_path / "j.wal")

    async def scenario():
        journal, _ = Journal.open(path)

        def boom(blob, need_sync):
            raise OSError(28, "No space left on device")

        monkeypatch.setattr(journal, "_write_sync", boom)
        fired = []
        journal.append(
            "finish", {"id": 1, "ckey": "", "cjid": 1, "mode": "min",
                       "n": 0, "h": "0", "found": True, "s": 1},
            on_durable=lambda: fired.append("first"),
        )
        await journal.flush()
        assert fired == ["first"]
        assert journal._failed
        # later appends short-circuit but still release their replies
        journal.append(
            "finish", {"id": 2, "ckey": "", "cjid": 2, "mode": "min",
                       "n": 0, "h": "0", "found": True, "s": 1},
            on_durable=lambda: fired.append("second"),
        )
        assert fired == ["first", "second"]
        await journal.aclose()

    asyncio.run(scenario())


def test_journal_compaction_preserves_state(tmp_path):
    path = str(tmp_path / "j.wal")

    async def scenario():
        journal, state = Journal.open(path, compact_bytes=512)
        journal.snapshot_provider = lambda: state.snapshot_obj()
        req = _mk_request(jid=2, upper=9999, ckey="cc")
        state.apply({"k": "job", "id": 1, "req": request_to_obj(req)})
        journal.append("job", {"id": 1, "req": request_to_obj(req)})
        for i in range(40):
            rec = {
                "k": "settle", "id": 1, "lo": 100 * i,
                "hi": 100 * i + 49, "h": "ff", "n": 100 * i, "s": 50,
            }
            state.apply(rec)
            journal.append("settle", dict(rec))
            await asyncio.sleep(0)
        await journal.flush()
        assert journal.stats["compactions"] >= 1
        await journal.aclose()
        return state

    state = asyncio.run(scenario())
    _, recovered = Journal.open(path)
    assert (
        recovered.jobs[1].remaining == state.jobs[1].remaining
        and recovered.jobs[1].hashes_done == state.jobs[1].hashes_done
    )


# ---------------------------------------------------------------------------
# LSP boot epoch: a restarted server is a FRESH session (satellite #1)
# ---------------------------------------------------------------------------

def test_server_restart_mid_connection_is_a_fresh_session():
    """Regression (issue satellite): a client whose server restarts on
    the SAME port must never resume old sequence state — the old
    connection dies promptly via the reset epoch-ack (long before its
    own silence timeout, which this test's params push out to seconds),
    no stale DATA is ever delivered to the new incarnation, and a
    redial sees a different boot epoch with sequence numbering starting
    over."""
    # epoch_limit high enough that silence-detection CANNOT explain the
    # loss — only the boot-epoch reset can
    params = Params(
        epoch_limit=60, epoch_millis=50, window_size=8,
        max_backoff_interval=2, max_unacked_messages=8,
    )

    async def scenario():
        server1 = await LspServer.create(0, params)
        port = server1.port
        epoch1 = server1.boot_epoch
        client = await LspClient.connect("127.0.0.1", port, params)
        assert client.server_epoch == epoch1 != 0
        client.write(b"hello")
        conn_id, payload = await asyncio.wait_for(server1.read(), 5)
        assert payload == b"hello"
        # kill -9 the server: socket closed, no drain, no goodbyes
        server1.crash()
        await server1.endpoint.wait_closed()
        # same port, new incarnation
        server2 = None
        for _ in range(50):
            try:
                server2 = await LspServer.create(port, params)
                break
            except OSError:
                await asyncio.sleep(0.02)
        assert server2 is not None
        assert server2.boot_epoch != epoch1
        # the old client keeps talking (data + heartbeats). server2
        # must deliver NONE of it, and the reset ack must kill the old
        # session fast (well under the 3 s silence horizon).
        client.write(b"stale-data-for-the-old-incarnation")
        t0 = time.monotonic()
        with pytest.raises(LspConnectionLost) as exc_info:
            await asyncio.wait_for(client.read(), 2.5)
        assert time.monotonic() - t0 < 2.0
        assert "restarted" in str(exc_info.value)
        assert server2.read_nowait() is None  # no stale delivery
        # redial: fresh session against the new epoch, seq starts over
        client2 = await LspClient.connect("127.0.0.1", port, params)
        assert client2.server_epoch == server2.boot_epoch
        client2.write(b"fresh")
        conn_id2, payload2 = await asyncio.wait_for(server2.read(), 5)
        assert payload2 == b"fresh"
        await client.close(drain_timeout=0.2)
        await client2.close(drain_timeout=0.2)
        await server2.close(drain_timeout=0.2)

    run(scenario(), timeout=30.0)


# ---------------------------------------------------------------------------
# coordinator crash e2e (the acceptance drill)
# ---------------------------------------------------------------------------

class SlowMiner(CpuMiner):
    """CpuMiner throttled enough that jobs are reliably mid-flight when
    the coordinator dies (generator steps run on the executor thread,
    so the sleep never blocks the event loop)."""

    def __init__(self, batch=256, nap=0.003):
        super().__init__(batch=batch)
        self._nap = nap

    def mine(self, request):
        for item in super().mine(request):
            time.sleep(self._nap)
            yield item


async def _restart_coordinator(port, wal, **kwargs):
    for attempt in range(100):
        try:
            return await Coordinator.create(
                port, params=FAST, recover_from=wal, **kwargs
            )
        except OSError:
            await asyncio.sleep(0.02)
    raise AssertionError("could not rebind the coordinator port")


def test_crash_recovery_exactly_once_with_bound_clients(tmp_path):
    """The acceptance drill: kill -9 the coordinator mid-epoch with a
    miner fleet and two bound clients in flight, restart from the
    journal — both clients get exactly one answer each, the answers
    equal brute force (no acknowledged work lost, no corruption), and
    the fleet resumes with zero manual intervention."""
    wal = str(tmp_path / "coord.wal")
    upper = 8191
    payloads = [b"crash-client-a", b"crash-client-b"]

    async def scenario():
        coord = await Coordinator.create(
            params=FAST, chunk_size=512, recover_from=wal
        )
        port = coord.port
        serve = asyncio.ensure_future(coord.serve())
        miners = [
            asyncio.ensure_future(run_miner_reconnect(
                "127.0.0.1", port, SlowMiner(), params=FAST,
                base_backoff=0.05, max_backoff=0.4,
                rng=random.Random(100 + i),
            ))
            for i in range(3)
        ]
        await asyncio.sleep(0.2)
        subs = [
            asyncio.ensure_future(submit(
                "127.0.0.1", port,
                Request(job_id=70 + i, mode=PowMode.MIN, lower=0,
                        upper=upper, data=payloads[i]),
                params=FAST, client_key=f"crash-client-{i}",
                reconnect=True, base_backoff=0.05,
                rng=random.Random(i),
            ))
            for i in range(2)
        ]
        try:
            # both jobs mid-flight: some chunks settled, none finished
            t0 = time.monotonic()
            while coord.stats["results_accepted"] < 4:
                assert time.monotonic() - t0 < 20, "no progress pre-crash"
                await asyncio.sleep(0.01)
            assert coord.stats["jobs_done"] == 0, (
                "crash must land mid-job; slow the miners down"
            )
            # -- kill -9 -------------------------------------------------
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            endpoint = coord.server.endpoint
            coord.crash()
            await endpoint.wait_closed()
            # -- restart from the journal on the same port ---------------
            coord2 = await _restart_coordinator(port, wal, chunk_size=512)
            assert len(coord2._jobs) == 2, (
                "both mid-flight jobs must replay from the journal"
            )
            # settled coverage survived (a settle buffered inside the
            # batch window at the instant of death may be lost — that
            # range just re-mines), and work remains on both jobs
            assert sum(j.hashes_done for j in coord2._jobs.values()) > 0
            for job in coord2._jobs.values():
                assert job.ranges
            serve = asyncio.ensure_future(coord2.serve())
            # -- the fleet resumes unattended ----------------------------
            results = await asyncio.wait_for(asyncio.gather(*subs), 60.0)
            for i, res in enumerate(results):
                expect = brute_min(payloads[i], 0, upper)
                assert (res.hash_value, res.nonce) == expect
                assert res.found
                assert res.searched >= upper + 1 - 512 * 4  # sanity
            assert not coord2._jobs  # both retired
            return coord2
        finally:
            for t in miners + subs:
                t.cancel()
            await asyncio.gather(*miners, *subs, return_exceptions=True)
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            # coord (crashed) holds no resources; close the live one
            try:
                await coord2.close()
            except UnboundLocalError:
                await coord.close()

    run(scenario(), timeout=120.0)


def test_winner_survives_restart_and_dedups(tmp_path):
    """An ACKNOWLEDGED winner is never lost and never re-mined: answer
    a job, kill -9, restart from the journal, re-submit the same
    (client_key, job_id) — the identical Result comes straight from the
    journaled winners table with zero hashes spent."""
    wal = str(tmp_path / "coord.wal")
    upper = 2047
    data = b"dedup-me"
    req = Request(
        job_id=31, mode=PowMode.MIN, lower=0, upper=upper, data=data,
        client_key="dedup-client",
    )

    async def scenario():
        coord = await Coordinator.create(
            params=FAST, chunk_size=1024, recover_from=wal
        )
        port = coord.port
        serve = asyncio.ensure_future(coord.serve())
        miner = asyncio.ensure_future(run_miner_reconnect(
            "127.0.0.1", port, CpuMiner(), params=FAST, base_backoff=0.05,
        ))
        try:
            await asyncio.sleep(0.15)
            first = await asyncio.wait_for(
                submit("127.0.0.1", port, req, params=FAST), 30.0
            )
            assert (first.hash_value, first.nonce) == brute_min(
                data, 0, upper
            )
            # -- kill -9 + restart ---------------------------------------
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            endpoint = coord.server.endpoint
            coord.crash()
            await endpoint.wait_closed()
            coord2 = await _restart_coordinator(port, wal, chunk_size=1024)
            serve = asyncio.ensure_future(coord2.serve())
            assert not coord2._jobs  # nothing to re-mine
            again = await asyncio.wait_for(
                submit("127.0.0.1", port, req, params=FAST), 30.0
            )
            assert again == first
            assert coord2.stats["hashes"] == 0  # answered from the table
            return coord2
        finally:
            miner.cancel()
            await asyncio.gather(miner, return_exceptions=True)
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            try:
                await coord2.close()
            except UnboundLocalError:
                await coord.close()

    run(scenario(), timeout=90.0)


def test_client_rebind_mid_job_no_duplicate(tmp_path):
    """A durable client that dies and redials MID-JOB re-binds to the
    running job (no duplicate job is mined) and still gets its answer."""
    wal = str(tmp_path / "coord.wal")
    upper = 8191
    data = b"rebind-me"

    async def scenario():
        coord = await Coordinator.create(
            params=FAST, chunk_size=512, recover_from=wal
        )
        port = coord.port
        serve = asyncio.ensure_future(coord.serve())
        miner = asyncio.ensure_future(run_miner_reconnect(
            "127.0.0.1", port, SlowMiner(), params=FAST, base_backoff=0.05,
        ))
        try:
            await asyncio.sleep(0.15)
            req = Request(
                job_id=5, mode=PowMode.MIN, lower=0, upper=upper,
                data=data, client_key="rebinder",
            )
            # first client dies mid-job (hard: no goodbye)
            c1 = await LspClient.connect("127.0.0.1", port, FAST)
            from tpuminter.protocol import encode_msg
            c1.write(encode_msg(req))
            t0 = time.monotonic()
            while coord.stats["results_accepted"] < 2:
                assert time.monotonic() - t0 < 20
                await asyncio.sleep(0.01)
            c1.endpoint.close()  # kill -9 the client
            # second incarnation re-submits the same (ckey, job_id)
            result = await asyncio.wait_for(
                submit("127.0.0.1", port, req, params=FAST), 60.0
            )
            assert (result.hash_value, result.nonce) == brute_min(
                data, 0, upper
            )
            # exactly one job ever existed for the key
            assert coord.stats["jobs_done"] == 1
            assert coord._next_job_id == 2
        finally:
            miner.cancel()
            await asyncio.gather(miner, return_exceptions=True)
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            await coord.close()

    run(scenario(), timeout=90.0)


# ---------------------------------------------------------------------------
# loadgen crash scenario: the tier-1 gate (issue satellite)
# ---------------------------------------------------------------------------

def test_loadgen_crash_scenario_smoke(capsys):
    """Small-fleet crash drill wired into tier-1 next to the steady
    ``--smoke`` gate: kill the journaled coordinator mid-burst, restart
    from the journal, and require an exactly-once answer ledger plus an
    unattended fleet resumption."""
    rc = loadgen.main([
        "--scenario", "crash", "--miners", "4", "--clients", "4",
        "--duration", "1.5", "--smoke", "--json",
    ])
    out = capsys.readouterr().out
    assert rc == 0, f"crash gate failed: {out}"
    metrics = _json.loads(out.splitlines()[0])
    assert metrics["answered"] > 0
    assert metrics["answers_lost"] == 0
    assert metrics["answers_duplicated"] == 0
    assert metrics["restart_to_first_assign_ms"] < 10_000
    # the journal actually carried state across the restart
    assert metrics["recovered_winners"] > 0
    assert metrics["journal"]["records"] > 0


# ---------------------------------------------------------------------------
# pipelined dispatch × crash recovery (ISSUE 4 satellite): kill -9 with
# depth-2 queues in flight; replay re-mines exactly the un-settled ranges
# ---------------------------------------------------------------------------

def test_pipelined_crash_replay_remines_exactly_the_unsettled_ranges(
    tmp_path,
):
    """A depth-2 miner holds TWO chunks when the coordinator dies, one
    of them settled pre-crash. Replay must (a) show the pipeline really
    was ≥2 deep, (b) rebuild remaining coverage as full-range minus the
    settled chunk ONLY (in-flight pipeline chunks re-mine — they never
    settled), and (c) a recovered coordinator + fresh miner then
    re-mines exactly those nonces, no more, no fewer, with the final
    fold brute-force exact across the crash."""
    from tpuminter.protocol import (
        Assign, Join, Result, Setup, decode_msg, encode_msg,
    )

    wal = str(tmp_path / "coordinator.wal")
    data = b"pipelined crash"
    upper = 4095
    chunk = 1024

    async def scenario():
        coord = await Coordinator.create(
            params=FAST, chunk_size=chunk, recover_from=wal
        )
        serve = asyncio.ensure_future(coord.serve())
        w = await LspClient.connect("127.0.0.1", coord.port, FAST)
        w.write(encode_msg(Join(backend="manual", lanes=1, codec="bin")))
        client = await LspClient.connect("127.0.0.1", coord.port, FAST)
        client.write(encode_msg(Request(
            job_id=31, mode=PowMode.MIN, lower=0, upper=upper, data=data,
            client_key="pipeline-ck",
        )))
        # the single miner must receive a Setup and TWO Assigns before
        # answering anything — the depth-2 pipeline in flight
        assigns = []
        while len(assigns) < 2:
            msg = decode_msg(await asyncio.wait_for(w.read(), 10))
            if isinstance(msg, Assign):
                assigns.append(msg)
            else:
                assert isinstance(msg, Setup)
        a1, a2 = assigns
        assert (a1.lower, a1.upper) == (0, chunk - 1)
        assert (a2.lower, a2.upper) == (chunk, 2 * chunk - 1)
        # settle ONLY the first chunk (a verifiable claim: the true
        # minimum of its range)
        h1, n1 = brute_min(data, a1.lower, a1.upper)
        w.write(encode_msg(Result(
            a1.job_id, PowMode.MIN, n1, h1, found=True,
            searched=a1.upper - a1.lower + 1, chunk_id=a1.chunk_id,
        ), binary=True))
        # wait for the settle record to reach the OS (crash() drops the
        # in-memory buffer; a flushed record survives kill -9)
        deadline = time.monotonic() + 5
        while coord._journal._buffer or coord._journal.stats["records"] < 3:
            assert time.monotonic() < deadline, coord._journal.stats
            await asyncio.sleep(0.01)
        assert coord.stats["dispatches_pipelined"] >= 1
        # -- kill -9 ----------------------------------------------------
        serve.cancel()
        await asyncio.gather(serve, return_exceptions=True)
        coord.crash()
        await coord.server.endpoint.wait_closed()
        await w.close(drain_timeout=0.1)

        # -- pure replay: coverage is full minus the settled chunk -----
        with open(wal, "rb") as fh:
            records, _ = scan(fh.read())
        state = replay(records)
        [job] = state.jobs.values()
        assert merge_ranges(job.remaining) == [(chunk, upper)]
        assert job.best == (h1, n1)
        assert job.hashes_done == chunk

        # -- recovered coordinator re-mines EXACTLY the rest -----------
        coord2 = await Coordinator.create(
            params=FAST, chunk_size=chunk, recover_from=wal
        )
        serve2 = asyncio.ensure_future(coord2.serve())
        miner2 = asyncio.ensure_future(run_miner_reconnect(
            "127.0.0.1", coord2.port, CpuMiner(), params=FAST, max_dials=1,
        ))
        try:
            result = await asyncio.wait_for(submit(
                "127.0.0.1", coord2.port,
                Request(job_id=31, mode=PowMode.MIN, lower=0, upper=upper,
                        data=data, client_key="pipeline-ck"),
                params=FAST,
            ), 30.0)
            assert (result.hash_value, result.nonce) == brute_min(
                data, 0, upper
            )
            assert result.searched == upper + 1  # pre-crash + re-mined
            # the re-mine covered exactly the un-settled nonces
            assert coord2.stats["hashes"] == upper + 1 - chunk
        finally:
            miner2.cancel()
            serve2.cancel()
            await asyncio.gather(miner2, serve2, return_exceptions=True)
            await coord2.close()
            await client.close(drain_timeout=0.1)

    run(scenario(), timeout=60.0)


def test_rolled_job_survives_crash_with_batched_path(tmp_path):
    """Rolled e2e through the durable coordinator (ISSUE 7): a rolled
    job at brute-force-checkable difficulty survives a mid-job kill -9
    + journal replay through the batched sweep (JaxMiner
    roll_batch=3), and the reconnecting client gets exactly one answer —
    the exact global minimum, equal to hashlib brute force."""
    import struct

    import numpy as np

    from tpuminter.jax_worker import JaxMiner

    wal = str(tmp_path / "rolled.wal")
    nb, ens = 9, 4  # 2048 global indices, 512-nonce segments
    rng = np.random.RandomState(11)
    prefix, suffix = rng.bytes(41), rng.bytes(60)
    branch = (rng.bytes(32), rng.bytes(32))
    hdr80 = chain.GENESIS_HEADER.pack()
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    want = min(
        (
            chain.hash_to_int(chain.dsha256(
                chain.rolled_header(hdr80, cb, branch, en).pack()[:76]
                + struct.pack("<I", n)
            )),
            (en << nb) | n,
        )
        for en in range(ens)
        for n in range(1 << nb)
    )
    req = Request(
        job_id=77, mode=PowMode.TARGET, lower=0, upper=(ens << nb) - 1,
        header=hdr80, target=1,  # unbeatable: must exhaust + min-fold
        coinbase_prefix=prefix, coinbase_suffix=suffix,
        extranonce_size=4, branch=branch, nonce_bits=nb,
    )

    class SlowJaxMiner(JaxMiner):
        """Batched rolled miner throttled so the crash lands mid-job."""

        def mine(self, request):
            for item in super().mine(request):
                time.sleep(0.05)
                yield item

    async def scenario():
        coord = await Coordinator.create(
            params=FAST, chunk_size=256, recover_from=wal
        )
        port = coord.port
        serve = asyncio.ensure_future(coord.serve())
        miners = [
            asyncio.ensure_future(run_miner_reconnect(
                "127.0.0.1", port,
                SlowJaxMiner(batch=128, roll_batch=3, lanes=1),
                params=FAST, base_backoff=0.05, max_backoff=0.4,
                rng=random.Random(200 + i),
            ))
            for i in range(2)
        ]
        await asyncio.sleep(0.2)
        sub = asyncio.ensure_future(submit(
            "127.0.0.1", port, req, params=FAST,
            client_key="rolled-crash-client", reconnect=True,
            base_backoff=0.05, rng=random.Random(42),
        ))
        coord2 = None
        try:
            t0 = time.monotonic()
            while coord.stats["results_accepted"] < 2:
                assert time.monotonic() - t0 < 30, "no progress pre-crash"
                await asyncio.sleep(0.01)
            assert coord.stats["jobs_done"] == 0, (
                "crash must land mid-job; slow the miners down"
            )
            # -- kill -9 -------------------------------------------------
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            endpoint = coord.server.endpoint
            coord.crash()
            await endpoint.wait_closed()
            coord2 = await _restart_coordinator(port, wal, chunk_size=256)
            assert len(coord2._jobs) == 1  # the rolled job replayed
            serve = asyncio.ensure_future(coord2.serve())
            res = await asyncio.wait_for(sub, 90.0)
            assert not res.found
            assert (res.hash_value, res.nonce) == want
            assert res.searched >= (ens << nb) - 256 * 2  # replay re-mines
            assert not coord2._jobs
        finally:
            for t in miners + [sub]:
                t.cancel()
            await asyncio.gather(*miners, sub, return_exceptions=True)
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            if coord2 is not None:
                await coord2.close()
            else:
                await coord.close()

    run(scenario(), timeout=150.0)


# ---------------------------------------------------------------------------
# admission state is durable (ISSUE 19 satellite)
# ---------------------------------------------------------------------------

def test_quota_buckets_survive_crash_recovery(tmp_path):
    """A tenant's token bucket is part of the recovered state: admit 4
    of a burst-6 budget, kill -9, restart from the journal — the tenant
    resumes at ~2 tokens (never a fresh burst: a crash must not be a
    quota-reset button), its strike count rides along, and an identity
    the journal never saw still gets the full burst. The refill clock
    restarting at boot only under-grants (rate here is ~0 anyway)."""
    from tpuminter.journal import scan_file
    from tpuminter.protocol import encode_msg

    wal = str(tmp_path / "quota.wal")

    async def scenario():
        coord = await Coordinator.create(
            params=FAST, chunk_size=512, recover_from=wal,
            quota_rate=0.001, quota_burst=6,
        )
        port = coord.port
        serve = asyncio.ensure_future(coord.serve())
        coord2 = None
        client = None
        try:
            # no miners on purpose: admission happens at submission,
            # the jobs just queue — this test is about the bucket
            client = await LspClient.connect("127.0.0.1", port, FAST)
            for jid in range(1, 5):
                client.write(encode_msg(Request(
                    job_id=jid, mode=PowMode.MIN, lower=0, upper=4095,
                    data=b"quota-%d" % jid, client_key="tenant-q",
                )))
            t0 = time.monotonic()
            while len(coord._jobs) < 4:
                assert time.monotonic() - t0 < 10, "submissions lost"
                await asyncio.sleep(0.01)
            tok, _, strikes = coord._buckets["tenant-q"]
            assert tok == pytest.approx(2.0, abs=0.01)
            # flush the dirty bucket the way the rate ticker does, then
            # hold the crash until the record is REALLY on disk
            coord._journal_quota()
            t0 = time.monotonic()
            while not replay(scan_file(wal)).quota:
                assert time.monotonic() - t0 < 10, "quota record unwritten"
                await asyncio.sleep(0.02)
            # -- kill -9 -------------------------------------------------
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            endpoint = coord.server.endpoint
            coord.crash()
            await endpoint.wait_closed()
            # -- restart from the journal --------------------------------
            coord2 = await _restart_coordinator(
                port, wal, quota_rate=0.001, quota_burst=6
            )
            assert "tenant-q" in coord2._buckets, (
                "the tenant's bucket must survive the crash"
            )
            tok2, _, strikes2 = coord2._buckets["tenant-q"]
            assert tok2 == pytest.approx(tok, abs=0.01)
            assert strikes2 == strikes
            assert "tenant-fresh" not in coord2._buckets  # full burst due
        finally:
            if client is not None:
                await client.close(drain_timeout=0.1)
            serve.cancel()
            await asyncio.gather(serve, return_exceptions=True)
            if coord2 is not None:
                await coord2.close()

    run(scenario(), timeout=60.0)
