"""End-to-end role tests (SURVEY.md §4 "Part B" strategy): coordinator +
miners + clients on localhost in one process; correctness is asserted
against brute-force ground truth; worker death mid-job must not lose or
corrupt results ("results must survive worker death")."""

import asyncio
import struct

import pytest

from tpuminter import chain
from tpuminter.client import submit
from tpuminter.coordinator import Coordinator
from tpuminter.lsp import Params
from tpuminter.protocol import PowMode, Request, Result
from tpuminter.worker import CpuMiner, run_miner

FAST = Params(
    epoch_limit=5,
    epoch_millis=50,
    window_size=32,
    max_backoff_interval=2,
    max_unacked_messages=32,
)


def run(coro, timeout=60.0):
    async def wrapped():
        return await asyncio.wait_for(coro, timeout)

    return asyncio.run(wrapped())


def brute_min(data: bytes, lower: int, upper: int):
    best = min((chain.toy_hash(data, n), n) for n in range(lower, upper + 1))
    return best  # (hash, nonce)


class Cluster:
    """Coordinator + miner tasks wired up on localhost."""

    def __init__(self, coordinator):
        self.coord = coordinator
        self.serve_task = asyncio.ensure_future(coordinator.serve())
        self.miner_tasks = []

    @classmethod
    async def create(cls, n_miners=1, chunk_size=4096, miner_factory=CpuMiner,
                     **coord_kwargs):
        coord = await Coordinator.create(
            params=FAST, chunk_size=chunk_size, **coord_kwargs
        )
        self = cls(coord)
        for _ in range(n_miners):
            await self.add_miner(miner_factory())
        return self

    async def add_miner(self, miner):
        task = asyncio.ensure_future(
            run_miner("127.0.0.1", self.coord.port, miner, params=FAST)
        )
        self.miner_tasks.append(task)
        # let the Join land before work is submitted
        await asyncio.sleep(0.05)
        return task

    async def kill_miner(self, index):
        """Hard-kill a miner: cancel its task; no goodbye to the server.

        The coordinator only learns of the death through epoch-based
        liveness, exactly like a crashed reference miner process.
        """
        task = self.miner_tasks[index]
        task.cancel()
        try:
            await task
        except (asyncio.CancelledError, Exception):
            pass

    async def close(self):
        for t in self.miner_tasks:
            t.cancel()
        self.serve_task.cancel()
        await asyncio.gather(*self.miner_tasks, self.serve_task, return_exceptions=True)
        await self.coord.close()


# ---------------------------------------------------------------------------
# toy (MIN) mode — reference user story
# ---------------------------------------------------------------------------

def test_single_miner_min_mode_matches_brute_force():
    async def scenario():
        cluster = await Cluster.create(n_miners=1)
        try:
            req = Request(job_id=7, mode=PowMode.MIN, lower=0, upper=9999,
                          data=b"hello bitcoin")
            result = await submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            want_hash, want_nonce = brute_min(b"hello bitcoin", 0, 9999)
            assert result.job_id == 7
            assert (result.hash_value, result.nonce) == (want_hash, want_nonce)
            assert result.found
        finally:
            await cluster.close()

    run(scenario())


def test_three_miners_split_one_job():
    async def scenario():
        cluster = await Cluster.create(n_miners=3, chunk_size=1024)
        try:
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=20_000,
                          data=b"parallel")
            result = await submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            assert (result.hash_value, result.nonce) == brute_min(b"parallel", 0, 20_000)
            # the job really was split across chunks
            assert cluster.coord.stats["hashes"] == 20_001
        finally:
            await cluster.close()

    run(scenario())


def test_concurrent_clients_round_robin():
    async def scenario():
        cluster = await Cluster.create(n_miners=2, chunk_size=1024)
        try:
            reqs = [
                Request(job_id=i, mode=PowMode.MIN, lower=0, upper=8000,
                        data=f"job-{i}".encode())
                for i in range(3)
            ]
            results = await asyncio.gather(
                *(submit("127.0.0.1", cluster.coord.port, r, params=FAST) for r in reqs)
            )
            for i, result in enumerate(results):
                assert result.job_id == i
                want = brute_min(f"job-{i}".encode(), 0, 8000)
                assert (result.hash_value, result.nonce) == want
        finally:
            await cluster.close()

    run(scenario())


# ---------------------------------------------------------------------------
# worker death — the core recovery story
# ---------------------------------------------------------------------------

def test_miner_death_mid_job_requeues_and_completes():
    async def scenario():
        cluster = await Cluster.create(n_miners=2, chunk_size=1024)
        try:
            data = b"survive the death"
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=400_000, data=data)
            submit_task = asyncio.ensure_future(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            )
            await asyncio.sleep(0.1)  # both miners are mid-chunk now
            await cluster.kill_miner(0)
            result = await submit_task
            assert (result.hash_value, result.nonce) == brute_min(data, 0, 400_000)
            assert cluster.coord.stats["chunks_requeued"] >= 1
        finally:
            await cluster.close()

    run(scenario())


def test_all_miners_die_then_new_miner_joins():
    async def scenario():
        cluster = await Cluster.create(n_miners=1, chunk_size=1024)
        try:
            data = b"late joiner saves the day"
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=150_000, data=data)
            submit_task = asyncio.ensure_future(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            )
            await asyncio.sleep(0.1)
            await cluster.kill_miner(0)  # now zero miners; job must stall, not die
            await asyncio.sleep(0.5)     # past the death-detection horizon
            assert not submit_task.done()
            await cluster.add_miner(CpuMiner())  # elasticity: join mid-job
            result = await submit_task
            assert (result.hash_value, result.nonce) == brute_min(data, 0, 150_000)
        finally:
            await cluster.close()

    run(scenario())


def test_client_death_drops_job_and_coordinator_survives():
    async def scenario():
        cluster = await Cluster.create(n_miners=1, chunk_size=512)
        try:
            from tpuminter.lsp import LspClient
            from tpuminter.protocol import encode_msg

            doomed = await LspClient.connect("127.0.0.1", cluster.coord.port, FAST)
            doomed.write(encode_msg(
                Request(job_id=1, mode=PowMode.MIN, lower=0, upper=500_000,
                        data=b"abandoned")
            ))
            await asyncio.sleep(0.15)
            await doomed.close()  # client vanishes mid-job
            # coordinator must still serve a healthy client
            req = Request(job_id=2, mode=PowMode.MIN, lower=0, upper=2000, data=b"ok")
            result = await submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            assert (result.hash_value, result.nonce) == brute_min(b"ok", 0, 2000)
        finally:
            await cluster.close()

    run(scenario())


# ---------------------------------------------------------------------------
# TARGET mode — real Bitcoin semantics (capability delta, BASELINE.json:6-8)
# ---------------------------------------------------------------------------

def test_target_mode_finds_genesis_nonce():
    async def scenario():
        cluster = await Cluster.create(n_miners=2, chunk_size=256)
        try:
            genesis_nonce = chain.GENESIS_HEADER.nonce
            req = Request(
                job_id=1,
                mode=PowMode.TARGET,
                lower=genesis_nonce - 500,
                upper=genesis_nonce + 500,
                header=chain.GENESIS_HEADER.pack(),
                target=chain.bits_to_target(0x1D00FFFF),
            )
            result = await submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            assert result.found
            assert result.nonce == genesis_nonce
            digest = result.hash_value.to_bytes(32, "little")
            assert chain.hash_to_hex(digest) == chain.GENESIS_HASH_HEX
        finally:
            await cluster.close()

    run(scenario())


def test_target_mode_exhausted_reports_best_effort():
    async def scenario():
        cluster = await Cluster.create(n_miners=1, chunk_size=256)
        try:
            req = Request(
                job_id=1,
                mode=PowMode.TARGET,
                lower=0,
                upper=999,  # range with no winner at genesis difficulty
                header=chain.GENESIS_HEADER.pack(),
                target=chain.bits_to_target(0x1D00FFFF),
            )
            result = await submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            assert not result.found
            # best-effort minimum is still reported, and is reproducible
            prefix = chain.GENESIS_HEADER.pack()[:76]
            want = min(
                (chain.hash_to_int(chain.dsha256(prefix + struct.pack("<I", n))), n)
                for n in range(1000)
            )
            assert (result.hash_value, result.nonce) == want
        finally:
            await cluster.close()

    run(scenario())


def test_target_mode_early_exit_cancels_remaining_work():
    async def scenario():
        # easy target: ~1/16 of hashes win, so a hit lands in the first
        # chunks and the job must finish WITHOUT sweeping the huge range.
        cluster = await Cluster.create(n_miners=2, chunk_size=1024)
        try:
            easy_target = (1 << 252) - 1
            req = Request(
                job_id=1,
                mode=PowMode.TARGET,
                lower=0,
                upper=50_000_000,  # would take minutes to sweep on CPU
                header=chain.GENESIS_HEADER.pack(),
                target=easy_target,
            )
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST), 20.0
            )
            assert result.found
            prefix = chain.GENESIS_HEADER.pack()[:76]
            digest = chain.dsha256(prefix + struct.pack("<I", result.nonce))
            assert chain.hash_to_int(digest) == result.hash_value
            assert result.hash_value <= easy_target
            # early exit: nowhere near the full range was searched
            assert cluster.coord.stats["hashes"] < 1_000_000
        finally:
            await cluster.close()

    run(scenario())


def test_client_death_dispatches_other_clients_queued_jobs():
    """Regression (ADVICE.md r1 / VERDICT r2 weak #1a): when a client
    dies, its cancelled miners go idle — a second client's queued job
    must be dispatched to them immediately, not stall until an unrelated
    event arrives."""

    async def scenario():
        # one miner, chunk big enough that client A's whole job is a
        # single long-running chunk keeping the miner busy
        cluster = await Cluster.create(
            n_miners=1, chunk_size=4_000_000,
            miner_factory=lambda: CpuMiner(batch=512),
        )
        try:
            from tpuminter.lsp import LspClient
            from tpuminter.protocol import encode_msg

            doomed = await LspClient.connect("127.0.0.1", cluster.coord.port, FAST)
            doomed.write(encode_msg(
                Request(job_id=1, mode=PowMode.MIN, lower=0, upper=3_999_999,
                        data=b"doomed job")
            ))
            await asyncio.sleep(0.2)  # miner is now deep in A's chunk
            # client B's job queues behind A's in-flight chunk
            req_b = Request(job_id=2, mode=PowMode.MIN, lower=0, upper=2000,
                            data=b"waiting job")
            submit_b = asyncio.ensure_future(
                submit("127.0.0.1", cluster.coord.port, req_b, params=FAST)
            )
            await asyncio.sleep(0.2)
            assert not submit_b.done()
            await doomed.close()  # A dies; its chunk is cancelled
            # B's job must now complete with NO further cluster events
            result = await asyncio.wait_for(submit_b, 15.0)
            assert (result.hash_value, result.nonce) == brute_min(b"waiting job", 0, 2000)
        finally:
            await cluster.close()

    run(scenario())


def test_forged_found_result_is_rejected_and_liar_evicted():
    """Regression (ADVICE.md r1 / VERDICT r2 weak #1b): a worker claiming
    found=True with a hash no nonce produces must not finish the job; the
    chunk is requeued, and a worker that keeps forging is evicted
    (bounding the requeue ping-pong) so an honest miner's answer wins."""

    async def scenario():
        cluster = await Cluster.create(n_miners=0)
        try:
            from tpuminter.coordinator import MAX_REJECTIONS
            from tpuminter.lsp import LspClient
            from tpuminter.protocol import (
                Assign, Join, Result, Setup, decode_msg, encode_msg,
            )

            evil = await LspClient.connect("127.0.0.1", cluster.coord.port, FAST)
            evil.write(encode_msg(Join(backend="evil", lanes=1)))

            async def forge_forever():
                # answer every dispatch with an impossible winner
                modes = {}
                while True:
                    msg = decode_msg(await evil.read())
                    if isinstance(msg, Setup):
                        modes[msg.request.job_id] = msg.request.mode
                    elif isinstance(msg, Assign):
                        evil.write(encode_msg(Result(
                            msg.job_id, modes[msg.job_id], nonce=msg.lower,
                            hash_value=0, found=True, searched=1,
                            chunk_id=msg.chunk_id,
                        )))

            evil_task = asyncio.ensure_future(forge_forever())
            await asyncio.sleep(0.05)

            genesis_nonce = chain.GENESIS_HEADER.nonce
            req = Request(
                job_id=1,
                mode=PowMode.TARGET,
                lower=genesis_nonce - 500,
                upper=genesis_nonce + 500,
                header=chain.GENESIS_HEADER.pack(),
                target=chain.bits_to_target(0x1D00FFFF),
            )
            submit_task = asyncio.ensure_future(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            )
            await asyncio.sleep(0.5)
            # forged winners must NOT have reached the client, and the
            # liar must have been evicted after MAX_REJECTIONS strikes
            assert not submit_task.done()
            assert cluster.coord.stats["results_rejected"] == MAX_REJECTIONS
            # an honest miner completes the requeued work
            await cluster.add_miner(CpuMiner())
            result = await asyncio.wait_for(submit_task, 30.0)
            assert result.found and result.nonce == genesis_nonce
            digest = result.hash_value.to_bytes(32, "little")
            assert chain.hash_to_hex(digest) == chain.GENESIS_HASH_HEX
            evil_task.cancel()
        finally:
            await cluster.close()

    run(scenario())


def test_refused_assign_requeues_and_resends_setup():
    """The template split's recovery seam (code-review r4): a worker
    whose template cache lost a live job Refuses the bare Assign; the
    coordinator requeues the chunk, re-ships the Setup, and the job
    still completes exactly — no wedged busy-forever miner."""

    async def scenario():
        cluster = await Cluster.create(n_miners=0, chunk_size=4096)
        from tpuminter.lsp import LspClient
        from tpuminter.protocol import (
            Assign, Join, Refuse, Result, Setup, decode_msg, encode_msg,
        )
        try:
            w = await LspClient.connect("127.0.0.1", cluster.coord.port, FAST)
            w.write(encode_msg(Join(backend="flaky", lanes=1)))
            setups = []

            async def act():
                refused = False
                templates = {}
                while True:
                    msg = decode_msg(await w.read())
                    if isinstance(msg, Setup):
                        setups.append(msg)
                        templates[msg.request.job_id] = msg.request
                    elif isinstance(msg, Assign):
                        if not refused:
                            refused = True
                            templates.pop(msg.job_id, None)  # "evicted"
                            w.write(encode_msg(Refuse(msg.job_id, msg.chunk_id)))
                            continue
                        t = templates.get(msg.job_id)
                        if t is None:
                            # a pipelined second Assign dispatched before
                            # our Refuse landed: refuse it too, exactly
                            # like the real worker role would
                            w.write(encode_msg(Refuse(msg.job_id, msg.chunk_id)))
                            continue
                        h, n = brute_min(t.data, msg.lower, msg.upper)
                        w.write(encode_msg(Result(
                            msg.job_id, t.mode, n, h, found=True,
                            searched=msg.upper - msg.lower + 1,
                            chunk_id=msg.chunk_id,
                        )))

            task = asyncio.ensure_future(act())
            req = Request(job_id=9, mode=PowMode.MIN, lower=0, upper=9999,
                          data=b"refuse me")
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST), 30.0
            )
            assert (result.hash_value, result.nonce) == brute_min(
                b"refuse me", 0, 9999
            )
            assert len(setups) >= 2  # the template really was re-shipped
            assert cluster.coord.stats["chunks_requeued"] >= 1
            task.cancel()
            await w.close()
        finally:
            await cluster.close()

    run(scenario())


def test_verify_result_rejects_out_of_range_nonce():
    """A real hash of a nonce OUTSIDE the dispatched range must fail
    host verification — else a malicious auditor could hunt beyond its
    sub-range for a framing hash, and a forger could poison the min
    fold with out-of-range values (code-review r4)."""
    req = Request(job_id=1, mode=PowMode.MIN, lower=100, upper=200, data=b"x")
    below = Result(1, PowMode.MIN, 50, chain.toy_hash(b"x", 50))
    above = Result(1, PowMode.MIN, 201, chain.toy_hash(b"x", 201))
    inside = Result(1, PowMode.MIN, 150, chain.toy_hash(b"x", 150))
    assert not Coordinator._verify_result(req, below)
    assert not Coordinator._verify_result(req, above)
    assert Coordinator._verify_result(req, inside)


def test_under_search_audit_catches_lazy_worker(monkeypatch):
    """VERDICT r3 missing #4: a worker whose Results verify (real hash
    of a real nonce) but that never actually searches its ranges is
    caught by a sampled re-mine on another worker, evicted, and its
    chunks re-mined — the client still gets the exact answer."""
    from tpuminter import coordinator as coord_mod

    # full-chunk audits make conviction deterministic; the fixture
    # guarantees no chunk's argmin sits at its own lower bound (what the
    # lazy worker always claims)
    monkeypatch.setattr(coord_mod, "AUDIT_SAMPLE", 1024)
    data = b"audit me"
    for lo in range(0, 8192, 1024):
        assert brute_min(data, lo, lo + 1023)[1] != lo, lo

    async def scenario():
        cluster = await Cluster.create(
            n_miners=0, chunk_size=1024, audit_rate=1.0, audit_seed=5,
        )
        from tpuminter.lsp import LspClient, LspConnectionLost
        from tpuminter.protocol import (
            Assign, Join, Result, Setup, decode_msg, encode_msg,
        )
        try:
            lazy = await LspClient.connect("127.0.0.1", cluster.coord.port, FAST)
            lazy.write(encode_msg(Join(backend="lazy", lanes=1)))

            async def be_lazy():
                # instantly answer every dispatch with the (verifiable!)
                # hash of the range's first nonce — never searching
                modes = {}
                try:
                    while True:
                        msg = decode_msg(await lazy.read())
                        if isinstance(msg, Setup):
                            modes[msg.request.job_id] = msg.request
                        elif isinstance(msg, Assign):
                            req = modes[msg.job_id]
                            lazy.write(encode_msg(Result(
                                msg.job_id, req.mode, nonce=msg.lower,
                                hash_value=chain.toy_hash(req.data, msg.lower),
                                found=True,
                                searched=msg.upper - msg.lower + 1,
                                chunk_id=msg.chunk_id,
                            )))
                except LspConnectionLost:
                    pass  # evicted, as expected

            lazy_task = asyncio.ensure_future(be_lazy())
            await asyncio.sleep(0.05)
            await cluster.add_miner(CpuMiner(batch=256))

            req = Request(job_id=3, mode=PowMode.MIN, lower=0, upper=8191,
                          data=data)
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST), 30.0
            )
            # exact answer despite the lazy worker's garbage folds
            assert (result.hash_value, result.nonce) == brute_min(data, 0, 8191)
            assert cluster.coord.stats["audits_failed"] >= 1
            assert cluster.coord.stats["audits_done"] >= 1
            # the lazy worker is gone from the fleet
            stats = cluster.coord.worker_stats()
            assert all(s["backend"] != "lazy" for s in stats.values())
            lazy_task.cancel()
        finally:
            await cluster.close()

    run(scenario())


def test_cancelled_miners_are_redispatched():
    """Regression: a Cancel that lands mid-chunk must return the miner to
    the idle pool (a cancelled worker sends no Result, so nothing else
    frees it). chunk_size > CpuMiner.batch so cancels interrupt mid-mine
    — the production default geometry."""

    async def scenario():
        cluster = await Cluster.create(
            n_miners=2, chunk_size=50_000,
            miner_factory=lambda: CpuMiner(batch=512),
        )
        try:
            easy_target = (1 << 252) - 1
            for round_no in range(3):
                req = Request(
                    job_id=round_no,
                    mode=PowMode.TARGET,
                    lower=0,
                    upper=10_000_000,
                    header=chain.GENESIS_HEADER.pack(),
                    target=easy_target,
                )
                result = await asyncio.wait_for(
                    submit("127.0.0.1", cluster.coord.port, req, params=FAST), 15.0
                )
                assert result.found
            # after three early-exited jobs both miners must still be
            # usable: a MIN job that needs the whole range completes
            req = Request(job_id=99, mode=PowMode.MIN, lower=0, upper=5000,
                          data=b"still alive")
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST), 15.0
            )
            assert (result.hash_value, result.nonce) == brute_min(b"still alive", 0, 5000)
        finally:
            await cluster.close()

    run(scenario())


def test_worker_stats_after_job():
    """Observability (SURVEY.md §5; VERDICT r2 #7): after a job, the
    coordinator's per-worker snapshots account for every verified hash,
    with rate and liveness fields populated."""

    async def scenario():
        cluster = await Cluster.create(
            n_miners=2, chunk_size=1000,
            miner_factory=lambda: CpuMiner(batch=256),
        )
        try:
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=7999,
                          data=b"stats")
            result = await submit(
                "127.0.0.1", cluster.coord.port, req, params=FAST
            )
            assert result.found
            stats = cluster.coord.worker_stats()
            assert len(stats) == 2
            # MIN mode has no early exit: every nonce is searched exactly
            # once, and both workers got chunks (8 chunks, 2 workers)
            assert sum(s["hashes"] for s in stats.values()) == 8000
            for snap in stats.values():
                assert snap["backend"] == "cpu"
                assert snap["chunks_done"] >= 1
                assert snap["mhs"] > 0
                assert snap["idle_s"] is not None
                assert not snap["busy"]
        finally:
            await cluster.close()

    run(scenario())


def test_stats_endpoint_and_rate_line_mid_job(caplog):
    """VERDICT r3 weak #6: the aggregate observability surface — the
    HTTP JSON stats endpoint answers mid-job with busy workers and live
    counters, and the periodic rate line fires while work flows."""
    import json as _json
    import logging as _logging

    async def scenario():
        cluster = await Cluster.create(
            n_miners=2, chunk_size=1024, stats_interval=0.1,
            miner_factory=lambda: CpuMiner(batch=256),
        )
        try:
            port = await cluster.coord.start_stats_server(0)
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=500_000,
                          data=b"observe me")
            job = asyncio.ensure_future(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            )
            await asyncio.sleep(0.3)  # mid-job
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            writer.write(b"GET / HTTP/1.0\r\n\r\n")
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), 5.0)
            writer.close()
            head, _, body = raw.partition(b"\r\n\r\n")
            assert head.startswith(b"HTTP/1.0 200")
            snap = _json.loads(body)
            assert snap["jobs_active"] >= 1
            assert snap["stats"]["hashes"] >= 0
            assert len(snap["workers"]) == 2
            assert any(w["busy"] for w in snap["workers"].values())
            result = await asyncio.wait_for(job, 60.0)
            assert (result.hash_value, result.nonce) == brute_min(
                b"observe me", 0, 500_000
            )
        finally:
            await cluster.close()

    with caplog.at_level(_logging.INFO, logger="tpuminter.coordinator"):
        run(scenario())
    assert any("rate:" in rec.message for rec in caplog.records)


def test_chaos_drops_deaths_and_concurrent_clients():
    """Robustness under combined failure modes (SURVEY.md §4's
    drops+epochs long-running tests): 10% loss + 10% duplication + 10%
    reordering in BOTH directions at the coordinator's transport seam,
    a miner hard-killed mid-flight, a replacement joining mid-flight —
    three concurrent clients must all still get exact answers, with
    every retransmission and requeue happening under the storm."""

    async def scenario():
        cluster = await Cluster.create(
            n_miners=3, chunk_size=500,
            miner_factory=lambda: CpuMiner(batch=128),
        )
        try:
            endpoint = cluster.coord._server.endpoint
            endpoint.set_fault_rates(drop=0.10, dup=0.10, reorder=0.10)
            endpoint.reorder_delay = 0.02

            async def one_client(jid, data, upper):
                req = Request(job_id=jid, mode=PowMode.MIN, lower=0,
                              upper=upper, data=data)
                return await submit(
                    "127.0.0.1", cluster.coord.port, req, params=FAST
                )

            jobs = [
                asyncio.ensure_future(one_client(1, b"chaos-a", 200_000)),
                asyncio.ensure_future(one_client(2, b"chaos-b", 150_000)),
                asyncio.ensure_future(one_client(3, b"chaos-c", 120_000)),
            ]
            await asyncio.sleep(0.3)          # jobs in flight...
            # the kill must hit a LIVE cluster or this hollows out into
            # a plain concurrency test (r3 review)
            assert not all(j.done() for j in jobs), "jobs finished too fast"
            await cluster.kill_miner(0)       # one miner crashes
            await cluster.add_miner(CpuMiner(batch=128))  # elastic rejoin
            results = await asyncio.wait_for(asyncio.gather(*jobs), 90.0)
            for result, (data, upper) in zip(
                results,
                [(b"chaos-a", 200_000), (b"chaos-b", 150_000), (b"chaos-c", 120_000)],
            ):
                assert (result.hash_value, result.nonce) == brute_min(
                    data, 0, upper
                ), data
        finally:
            await cluster.close()

    run(scenario(), timeout=120.0)


def test_mixed_fleet_heterogeneous_backends():
    """A job split across cpu + jax miners (different backends, one
    interface — BASELINE.json:5's mixed-fleet story): the fold across
    heterogeneous workers must still be exact."""
    from tpuminter.jax_worker import JaxMiner

    async def scenario():
        cluster = await Cluster.create(n_miners=0, chunk_size=1500)
        await cluster.add_miner(CpuMiner(batch=256))
        await cluster.add_miner(JaxMiner(batch=1 << 12, lanes=1))
        try:
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=11_999,
                          data=b"mixed fleet")
            result = await submit(
                "127.0.0.1", cluster.coord.port, req, params=FAST
            )
            assert (result.hash_value, result.nonce) == brute_min(
                b"mixed fleet", 0, 11_999
            )
            stats = cluster.coord.worker_stats()
            assert sorted(s["backend"] for s in stats.values()) == ["cpu", "jax"]
            # both backends did verified work
            assert all(s["hashes"] > 0 for s in stats.values())
        finally:
            await cluster.close()

    run(scenario())


def test_pod_worker_death_requeues_to_cpu():
    """A whole-slice worker dying is just a (big) worker death: its
    chunk requeues and a surviving CPU miner completes the job — the
    slice-level failure-domain story (SURVEY.md §5)."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        pytest.skip("needs the fake 8-device CPU mesh")
    from tpuminter.parallel import make_mesh
    from tpuminter.pod_worker import PodMiner

    async def scenario():
        mesh = make_mesh(_jax.devices()[:8])
        cluster = await Cluster.create(n_miners=0, chunk_size=2000)
        await cluster.add_miner(
            PodMiner(mesh=mesh, slab_per_device=128, n_slabs=2, kernel="jnp")
        )
        await cluster.add_miner(CpuMiner(batch=256))
        try:
            # large enough that a warm pod can't finish before the kill
            # lands (a 10k job completed in <0.2 s once JAX was warm and
            # turned this into a flake)
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=149_999,
                          data=b"pod dies")
            job = asyncio.ensure_future(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST)
            )
            # kill the pod the moment it demonstrably holds a chunk
            for _ in range(2000):
                stats = cluster.coord.worker_stats()
                if any(s["backend"] == "pod" and s["busy"]
                       for s in stats.values()):
                    break
                await asyncio.sleep(0.005)
            else:
                raise AssertionError("pod never got a chunk")
            assert not job.done(), "job finished before the kill landed"
            await cluster.kill_miner(0)  # the whole "slice" goes down
            result = await asyncio.wait_for(job, 60.0)
            assert (result.hash_value, result.nonce) == brute_min(
                b"pod dies", 0, 149_999
            )
            # the death really cost a chunk (not an idle-miner kill)
            assert cluster.coord.stats["chunks_requeued"] >= 1
        finally:
            await cluster.close()

    run(scenario())


def test_straggler_hedging_rescues_slow_chunk():
    """Opt-in speculative backup dispatch: a chunk stuck on a stalled
    miner is duplicated onto idle capacity once nothing else is queued,
    the backup's verified Result wins, and the straggler is released
    with a Cancel — the job completes exactly despite a worker that
    never answers."""
    import time as _time

    from tpuminter.worker import Miner

    class StallMiner(Miner):
        backend = "cpu"
        lanes = 1

        def mine(self, request):
            while True:
                _time.sleep(0.05)  # forever "mining", never a Result
                yield None

    async def scenario():
        cluster = await Cluster.create(
            n_miners=0, chunk_size=3000, hedge_after=0.3
        )
        await cluster.add_miner(StallMiner())       # gets chunk [0, 2999]
        await cluster.add_miner(CpuMiner(batch=256))
        try:
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=5999,
                          data=b"hedge me")
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST),
                30.0,
            )
            assert (result.hash_value, result.nonce) == brute_min(
                b"hedge me", 0, 5999
            )
            assert cluster.coord.stats["chunks_hedged"] >= 1
        finally:
            await cluster.close()

    run(scenario())


def test_hedging_disabled_by_default_no_duplicates():
    """Without hedge_after, accounting stays exact (no duplicated
    work): the original semantics are untouched by the feature."""

    async def scenario():
        cluster = await Cluster.create(n_miners=2, chunk_size=1024)
        try:
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=20_000,
                          data=b"no hedge")
            result = await submit(
                "127.0.0.1", cluster.coord.port, req, params=FAST
            )
            assert (result.hash_value, result.nonce) == brute_min(
                b"no hedge", 0, 20_000
            )
            assert cluster.coord.stats["hashes"] == 20_001
            assert cluster.coord.stats["chunks_hedged"] == 0
        finally:
            await cluster.close()

    run(scenario())


def test_one_client_connection_many_jobs():
    """A single LSP connection may submit several Requests; each job's
    final Result echoes the client's own job_id so answers can arrive
    in any order and still be matched (the reference's client sends one
    request, but the protocol — and our scheduler — supports many)."""
    from tpuminter.lsp import LspClient
    from tpuminter.protocol import Result as ResultMsg
    from tpuminter.protocol import decode_msg, encode_msg

    async def scenario():
        cluster = await Cluster.create(n_miners=2, chunk_size=1024)
        try:
            conn = await LspClient.connect(
                "127.0.0.1", cluster.coord.port, FAST
            )
            jobs = {
                11: (b"multi-a", 9_000),
                22: (b"multi-b", 4_000),
                33: (b"multi-c", 6_500),
            }
            for jid, (data, upper) in jobs.items():
                conn.write(encode_msg(Request(
                    job_id=jid, mode=PowMode.MIN, lower=0, upper=upper,
                    data=data,
                )))
            got = {}
            while len(got) < len(jobs):
                msg = decode_msg(await conn.read())
                assert isinstance(msg, ResultMsg)
                got[msg.job_id] = msg
            await conn.close()
            for jid, (data, upper) in jobs.items():
                want = brute_min(data, 0, upper)
                assert (got[jid].hash_value, got[jid].nonce) == want, jid
        finally:
            await cluster.close()

    run(scenario())


# ---------------------------------------------------------------------------
# pipelined-worker dispatch granularity (Join.span)
# ---------------------------------------------------------------------------

def test_span_hint_sizes_chunks_to_multiple_spans():
    """A worker advertising a pipeline span gets chunks covering
    SPANS_PER_DISPATCH spans, so its slab pipeline never drains at a
    chunk boundary (PERF.md: single-span dispatch measured 9% slower);
    a lanes=1 budget of chunk_size=600 would otherwise carve 600-nonce
    crumbs for this device-class miner."""
    sizes = []

    class SpanMiner(CpuMiner):
        span = 5_000

        def mine(self, request):
            sizes.append(request.upper - request.lower + 1)
            yield from super().mine(request)

    async def scenario():
        cluster = await Cluster.create(
            n_miners=1, chunk_size=600, miner_factory=SpanMiner
        )
        try:
            req = Request(job_id=3, mode=PowMode.MIN, lower=0, upper=99_999,
                          data=b"span hint")
            result = await submit(
                "127.0.0.1", cluster.coord.port, req, params=FAST
            )
            want_hash, want_nonce = brute_min(b"span hint", 0, 99_999)
            assert (result.hash_value, result.nonce) == (want_hash, want_nonce)
        finally:
            await cluster.close()

    run(scenario())
    from tpuminter.coordinator import SPANS_PER_DISPATCH

    assert sizes, "miner never received a chunk"
    assert sum(sizes) == 100_000
    assert min(sizes) >= SPANS_PER_DISPATCH * SpanMiner.span


def test_huge_span_hint_cannot_monopolize_a_job():
    """lanes/span are unvalidated wire hints: a worker advertising an
    absurd span still never gets more than half a job in one dispatch,
    so a second worker can always participate (and a hedge backup's
    size class can always cover any chunk)."""
    sizes = []

    class GreedyMiner(CpuMiner):
        span = 1 << 31

        def mine(self, request):
            sizes.append(request.upper - request.lower + 1)
            yield from super().mine(request)

    async def scenario():
        cluster = await Cluster.create(
            n_miners=1, chunk_size=600, miner_factory=GreedyMiner
        )
        try:
            req = Request(job_id=4, mode=PowMode.MIN, lower=0, upper=99_999,
                          data=b"greedy")
            result = await submit(
                "127.0.0.1", cluster.coord.port, req, params=FAST
            )
            want = brute_min(b"greedy", 0, 99_999)
            assert (result.hash_value, result.nonce) == want
        finally:
            await cluster.close()

    run(scenario())
    assert len(sizes) >= 2
    assert max(sizes) <= 50_000
    assert sum(sizes) == 100_000


def test_client_sees_disconnected_when_coordinator_dies_mid_job():
    """Reference UX (SURVEY.md §3.1): a client blocked on its Result
    must learn of coordinator death through epoch liveness — submit
    raises LspConnectionLost (the CLI prints ``Disconnected`` on it,
    client.py:148) rather than hanging forever on a queued job."""
    from tpuminter.lsp import LspConnectionLost

    async def scenario():
        cluster = await Cluster.create(n_miners=0)  # job queues forever
        job = asyncio.ensure_future(submit(
            "127.0.0.1", cluster.coord.port,
            Request(job_id=9, mode=PowMode.MIN, lower=0, upper=10**6,
                    data=b"orphaned job"),
            params=FAST,
        ))
        closed = False
        try:
            await asyncio.sleep(0.3)  # connect + submit land
            assert not job.done(), (
                f"submit finished early: "
                f"{job.exception() if not job.cancelled() else 'cancelled'}"
            )
            await cluster.close()  # coordinator dies, no goodbye
            closed = True
            with pytest.raises(LspConnectionLost):
                await asyncio.wait_for(job, timeout=30)
        finally:
            if not closed:
                await cluster.close()
            if not job.done():
                job.cancel()
            await asyncio.gather(job, return_exceptions=True)

    run(scenario())


# ---------------------------------------------------------------------------
# reference-default LSP params (VERDICT r5 next #6: the last true
# coverage hole — every scenario above runs on FAST millisecond epochs)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_reference_default_params_survive_miner_death():
    """One full scenario on ``Params()`` DEFAULTS (epoch_limit 5,
    epoch_millis 2000, window_size 1 — the canonical reference
    vintage): coordinator + 2 miners + client, one miner hard-killed
    mid-job. Death detection takes 5 × 2 s of real time here, which is
    exactly the point — the window-1, seconds-scale regime is a
    different operating point of the same machine (stop-and-wait sends,
    heartbeat pacing, loss horizon) and nothing above exercises it."""

    async def scenario():
        defaults = Params()
        coord = await Coordinator.create(params=defaults)
        serve = asyncio.ensure_future(coord.serve())
        miners = [
            asyncio.ensure_future(run_miner(
                "127.0.0.1", coord.port, CpuMiner(batch=2048),
                params=defaults,
            ))
            for _ in range(2)
        ]
        try:
            await asyncio.sleep(1.0)  # both Joins land
            assert len(coord.worker_stats()) == 2
            data = b"reference defaults"
            req = Request(job_id=1, mode=PowMode.MIN, lower=0,
                          upper=600_000, data=data)
            job = asyncio.ensure_future(submit(
                "127.0.0.1", coord.port, req, params=defaults
            ))
            # kill a miner once BOTH demonstrably hold chunks (so the
            # victim's death provably costs an in-flight chunk)
            for _ in range(400):
                stats = coord.worker_stats()
                if len(stats) == 2 and all(
                    s["busy"] for s in stats.values()
                ):
                    break
                await asyncio.sleep(0.05)
            else:
                raise AssertionError("miners never both went busy")
            assert not job.done()
            victim = miners[0]
            victim.cancel()
            await asyncio.gather(victim, return_exceptions=True)
            # 10 s loss horizon + remaining mining, with slack for the
            # window-1 message pacing
            result = await asyncio.wait_for(job, 120.0)
            assert (result.hash_value, result.nonce) == brute_min(
                data, 0, 600_000
            )
            assert coord.stats["chunks_requeued"] >= 1
        finally:
            for m in miners:
                m.cancel()
            serve.cancel()
            await asyncio.gather(*miners, serve, return_exceptions=True)
            await coord.close()

    run(scenario(), timeout=180.0)


# ---------------------------------------------------------------------------
# long-lived coordinator soak (VERDICT r4 missing #3)
# ---------------------------------------------------------------------------

def test_coordinator_soak_50_jobs_drains_all_bookkeeping():
    """One coordinator through 50 mixed-mode jobs with every optional
    subsystem on at once — audits at rate 1.0, hedging armed, a lying
    worker evicted mid-run, a healthy worker hard-killed mid-run — then
    prove the process could run forever: every internal map (_jobs,
    _rotation, _audit_queue, _audits, per-miner chunks, per-client job
    sets) drains to empty and stats_snapshot reports zero queue depth.
    The reference's coordinator runs indefinitely; seconds-long
    scenarios alone cannot catch bookkeeping that leaks per job."""
    from tpuminter.lsp import LspClient, LspConnectionLost
    from tpuminter.protocol import Assign, Join, Setup, decode_msg, encode_msg

    data = b"soak job payload"
    gn = chain.GENESIS_HEADER.nonce
    diff1 = chain.bits_to_target(0x1D00FFFF)
    hdr = chain.GENESIS_HEADER.pack()

    def make_requests():
        reqs = []
        for i in range(50):
            jid = 100 + i
            kind = i % 10
            if kind == 8:  # TARGET that finds the genesis winner
                reqs.append((jid, Request(
                    job_id=jid, mode=PowMode.TARGET, lower=gn - 1200,
                    upper=gn + 800, header=hdr, target=diff1,
                ), ("target-found",)))
            elif kind == 9:  # TARGET exhausted (best-effort min)
                reqs.append((jid, Request(
                    job_id=jid, mode=PowMode.TARGET, lower=i * 100,
                    upper=i * 100 + 1499, header=hdr, target=1,
                ), ("target-miss",)))
            elif kind == 7:  # SCRYPT exhausted (memory-hard: slow). The
                # kill batch's scrypt job is bigger so the best-effort
                # chaos kill below has slow chunks to land on (the
                # PROVABLE requeue attribution is the separate
                # mute-worker phase after the soak loop).
                reqs.append((jid, Request(
                    job_id=jid, mode=PowMode.SCRYPT, lower=0,
                    upper=1199 if i == 27 else 59 + i,
                    header=hdr, target=1,
                ), ("scrypt",)))
            else:  # MIN with per-job payload and varying ranges
                lo = 37 * i
                reqs.append((jid, Request(
                    job_id=jid, mode=PowMode.MIN, lower=lo,
                    upper=lo + 2000 + 100 * (i % 5), data=data + bytes([i]),
                ), ("min",)))
        return reqs

    async def scenario():
        # batch=64 keeps yield (= cancellation) points dense: the mid-
        # soak hard-kill below must interrupt a chunk MID-COMPUTE, and a
        # miner that crunches a whole chunk in one synchronous step can
        # slip its Result out before task cancellation is delivered
        cluster = await Cluster.create(
            n_miners=3, chunk_size=512, audit_rate=1.0, audit_seed=11,
            hedge_after=0.25, miner_factory=lambda: CpuMiner(batch=64),
        )
        coord = cluster.coord
        try:
            # a verifiable-but-lying worker (the lazy pattern): answers
            # every MIN dispatch instantly with its range's first nonce
            liar = await LspClient.connect("127.0.0.1", coord.port, FAST)
            liar.write(encode_msg(Join(backend="liar", lanes=1)))

            async def be_lazy():
                modes = {}
                try:
                    while True:
                        msg = decode_msg(await liar.read())
                        if isinstance(msg, Setup):
                            modes[msg.request.job_id] = msg.request
                        elif isinstance(msg, Assign):
                            req = modes[msg.job_id]
                            if req.mode != PowMode.MIN:
                                continue  # stall non-MIN: hedging covers
                            liar.write(encode_msg(Result(
                                msg.job_id, req.mode, nonce=msg.lower,
                                hash_value=chain.toy_hash(req.data, msg.lower),
                                found=True,
                                searched=msg.upper - msg.lower + 1,
                                chunk_id=msg.chunk_id,
                            )))
                except LspConnectionLost:
                    pass  # evicted, as expected

            liar_task = asyncio.ensure_future(be_lazy())
            await asyncio.sleep(0.05)

            def true_result(req, msg):
                """Brute-force the exact answer for a small assign —
                the mute worker stays in good standing on audits."""
                lo, hi = msg.lower, msg.upper
                if req.mode == PowMode.MIN:
                    h, n = brute_min(req.data, lo, hi)
                    return Result(msg.job_id, req.mode, n, h, found=True,
                                  searched=hi - lo + 1, chunk_id=msg.chunk_id)
                fn = (chain.scrypt_hash if req.mode == PowMode.SCRYPT
                      else chain.dsha256)
                pre = req.header[:76]
                best = None
                for n in range(lo, hi + 1):
                    h = chain.hash_to_int(fn(pre + struct.pack("<I", n)))
                    if h <= req.target:
                        return Result(msg.job_id, req.mode, n, h, found=True,
                                      searched=n - lo + 1,
                                      chunk_id=msg.chunk_id)
                    if best is None or (h, n) < best:
                        best = (h, n)
                return Result(msg.job_id, req.mode, best[1], best[0],
                              found=False, searched=hi - lo + 1,
                              chunk_id=msg.chunk_id)

            async def start_mute():
                mute = await LspClient.connect(
                    "127.0.0.1", coord.port, FAST
                )
                mute.write(encode_msg(Join(backend="mute", lanes=1)))

                async def run_mute():
                    setups = {}
                    try:
                        while True:
                            msg = decode_msg(await mute.read())
                            if isinstance(msg, Setup):
                                setups[msg.request.job_id] = msg.request
                            elif isinstance(msg, Assign):
                                if msg.upper - msg.lower + 1 >= 400:
                                    continue  # stall the real job chunk
                                mute.write(encode_msg(
                                    true_result(setups[msg.job_id], msg)
                                ))
                    except LspConnectionLost:
                        pass

                task = asyncio.ensure_future(run_mute())
                await asyncio.sleep(0.05)
                return mute, task

            reqs = make_requests()
            results = {}
            for batch_start in range(0, len(reqs), 10):
                batch = reqs[batch_start:batch_start + 10]
                futures = [
                    asyncio.ensure_future(
                        submit("127.0.0.1", coord.port, req, params=FAST)
                    )
                    for _, req, _ in batch
                ]
                if batch_start == 20:
                    # hard-kill the whole cpu fleet mid-batch with
                    # simultaneous cancels (sequential kills let a
                    # victim finish a chunk during close-drain) — the
                    # chaos ingredient; requeue ATTRIBUTION has its own
                    # deterministic phase after the soak loop
                    victims = [t for t in cluster.miner_tasks
                               if not t.done()]
                    for t in victims:
                        t.cancel()
                    await asyncio.gather(*victims, return_exceptions=True)
                    for _ in range(3):
                        await cluster.add_miner(CpuMiner(batch=64))
                outs = await asyncio.gather(*futures)
                for (jid, _, _), out in zip(batch, outs):
                    results[jid] = out

            # deterministic death-requeue attribution, as its own phase
            # (during the soak batches, audit-first dispatch starves a
            # late joiner of job chunks ~20% of runs): a MUTE worker
            # that answers small assigns correctly (audits are <=
            # AUDIT_SAMPLE = 256 nonces, so it stays in good standing)
            # but STALLS any >= 400-nonce job chunk — held inflight
            # with no completion race possible. Closing its connection
            # must route that chunk through the COUNTED requeue path,
            # and the job then completes exact on the survivors.
            # Hedging is parked for this phase: the queue drains in
            # ~0.2 s (toy chunks are ~1 ms), after which a hedge copy
            # of the stalled chunk would win the race against epoch
            # loss and release it through the UNCOUNTED settle path —
            # the hedging subsystem doing its job, but not the path
            # under test here.
            coord._hedge_after = 1e9  # ticker re-reads it each cycle
            mute, mute_task = await start_mute()
            attribution = Request(
                job_id=999, mode=PowMode.MIN, lower=0, upper=511_999,
                data=b"requeue attribution",
            )
            fut = asyncio.ensure_future(submit(
                "127.0.0.1", coord.port, attribution, params=FAST
            ))
            for _ in range(3000):
                if any(
                    m.backend == "mute" and cid not in coord._audits
                    for m in coord._miners.values()
                    for cid in m.chunks
                ):
                    break
                await asyncio.sleep(0.01)
            else:
                dump = {
                    conn: (m.backend, dict(m.chunks),
                           sorted(c for c in m.chunks
                                  if c in coord._audits))
                    for conn, m in coord._miners.items()
                }
                raise AssertionError(
                    f"mute never stalled a job chunk; miners={dump} "
                    f"job999_done={fut.done()} "
                    f"snap={coord.stats_snapshot()['jobs_active']}"
                )
            requeued_before = coord.stats["chunks_requeued"]
            await mute.close(drain_timeout=0.05)
            mute_task.cancel()
            await asyncio.gather(mute_task, return_exceptions=True)
            out999 = await asyncio.wait_for(fut, 90)
            assert (out999.hash_value, out999.nonce) == brute_min(
                attribution.data, 0, 511_999
            )
            assert coord.stats["chunks_requeued"] > requeued_before

            # every job's answer is exact despite liar/death/hedges
            for jid, req, tag in reqs:
                out = results[jid]
                assert out.job_id == jid
                if tag[0] == "min":
                    want = brute_min(req.data, req.lower, req.upper)
                    assert (out.hash_value, out.nonce) == want, (jid, tag)
                    assert out.found
                elif tag[0] == "target-found":
                    assert out.found and out.nonce == gn
                elif tag[0] == "target-miss":
                    assert not out.found
                    want = min(
                        (chain.hash_to_int(chain.dsha256(
                            hdr[:76] + struct.pack("<I", n))), n)
                        for n in range(req.lower, req.upper + 1)
                    )
                    assert (out.hash_value, out.nonce) == want, jid
                else:  # scrypt exhausted: exact min of the range
                    want = min(
                        (chain.hash_to_int(chain.scrypt_hash(
                            hdr[:76] + struct.pack("<I", n))), n)
                        for n in range(req.lower, req.upper + 1)
                    )
                    assert not out.found
                    assert (out.hash_value, out.nonce) == want, jid

            # the liar was caught and evicted along the way
            assert coord.stats["audits_failed"] >= 1
            assert all(
                s["backend"] != "liar" for s in coord.worker_stats().values()
            )
            assert coord.stats["jobs_done"] >= 50

            # drain: audits may outlive their jobs by design; give the
            # fleet a bounded window to settle every trailing audit
            deadline = asyncio.get_event_loop().time() + 20.0
            while asyncio.get_event_loop().time() < deadline:
                snap = coord.stats_snapshot()
                busy = any(
                    w["busy"] for w in snap["workers"].values()
                )
                if (
                    snap["jobs_active"] == 0
                    and snap["chunks_queued"] == 0
                    and snap["audits_queued"] == 0
                    and not busy
                ):
                    break
                await asyncio.sleep(0.1)

            # the leak-free guarantee, on the raw internals
            assert coord._jobs == {}, coord._jobs
            assert not coord._rotation, coord._rotation
            assert not coord._audit_queue, coord._audit_queue
            assert coord._audits == {}, coord._audits
            for m in coord._miners.values():
                assert not m.chunks, (m.conn_id, dict(m.chunks))
            assert not any(coord._clients.values()), coord._clients
            snap = coord.stats_snapshot()
            assert snap["jobs_active"] == 0
            assert snap["chunks_queued"] == 0
            assert snap["audits_queued"] == 0
            liar_task.cancel()
            await asyncio.gather(liar_task, return_exceptions=True)
        finally:
            await cluster.close()

    run(scenario(), timeout=240.0)


# ---------------------------------------------------------------------------
# dispatch budget arithmetic (unit-level: the span-alignment rules)
# ---------------------------------------------------------------------------

def test_budget_span_alignment_and_caps():
    """ADVICE r4: chunk budgets for pipelined miners must be whole
    multiples of the worker's span (a chunk ending mid-span refills the
    pod pipeline once per chunk), including AFTER the half-job cap; the
    scrypt floor loses to the half-job cap on tiny jobs by design."""
    from tpuminter.coordinator import (
        SCRYPT_MIN_CHUNK, SPANS_PER_DISPATCH, _Job, _MinerState,
    )

    async def scenario():
        coord = await Coordinator.create(params=FAST, chunk_size=4096)
        try:
            def job(mode, lower, upper):
                kw = (dict(data=b"x") if mode == PowMode.MIN else
                      dict(header=chain.GENESIS_HEADER.pack(), target=1))
                return _Job(job_id=1, client_conn=1, client_job_id=1,
                            request=Request(job_id=1, mode=mode,
                                            lower=lower, upper=upper,
                                            **kw))

            def miner(lanes=1, span=0):
                return _MinerState(conn_id=9, backend="t", lanes=lanes,
                                   span=span)

            big = job(PowMode.MIN, 0, (1 << 32) - 1)

            # pipelined miner: budget is a whole number of spans and at
            # least SPANS_PER_DISPATCH of them
            m = miner(lanes=7, span=1000)
            b = coord._budget(m, big)
            assert b % 1000 == 0
            assert b >= SPANS_PER_DISPATCH * 1000

            # chunk_size*lanes dominating must still be span-aligned
            m2 = miner(lanes=1000, span=999)  # 4096*1000 not a multiple
            b2 = coord._budget(m2, big)
            assert b2 % 999 == 0 and b2 > 0

            # the half-job cap can land mid-span; the re-round restores
            # alignment while at least one whole span fits
            small = job(PowMode.MIN, 0, 2999)  # half-job cap ~1500
            m3 = miner(lanes=1000, span=700)
            b3 = coord._budget(m3, small)
            assert b3 == 1400  # capped to <=1500, re-rounded to 2x700
            # below one span the cap wins outright (exhaustion beats
            # alignment on jobs smaller than two spans)
            tiny = job(PowMode.MIN, 0, 999)
            b4 = coord._budget(m3, tiny)
            assert 0 < b4 <= 500

            # scrypt: divisor-scaled with the RPC-amortization floor...
            sc = job(PowMode.SCRYPT, 0, (1 << 20) - 1)
            b5 = coord._budget(miner(lanes=1), sc)
            assert b5 == SCRYPT_MIN_CHUNK
            # ...which the half-job anti-monopoly cap beats on tiny jobs
            sc_tiny = job(PowMode.SCRYPT, 0, 599)
            b6 = coord._budget(miner(lanes=1), sc_tiny)
            assert b6 == 300  # (599 + 2) // 2, under the 512 floor
        finally:
            await coord.close()

    run(scenario())


def test_cancel_interrupts_pipelined_scrypt_within_one_span():
    """Cancel-latency guard for the depth-2 double-buffered device loops
    (``search.pipeline_spans`` — VERDICT r5 weak #2): pipelining must
    not move the role loop's yield points, so a Cancel still lands
    within ONE resolved span — the speculative in-flight batch is
    abandoned, never waited for. Client A's effectively-unbounded scrypt
    job is cancelled by A's death mid-pipeline; client B's tiny MIN job
    must then complete promptly, which fails if the pipelined generator
    stops yielding between batches or drains its queue before noticing
    the Cancel."""

    async def scenario():
        import time as _time

        from tpuminter.jax_worker import JaxMiner

        # warm the (64,)-shaped scrypt compile OUTSIDE the timed
        # scenario so the cancel window measures batches, not XLA
        warm = JaxMiner(scrypt_batch=64)
        warm_req = Request(job_id=99, mode=PowMode.SCRYPT, lower=0, upper=63,
                           header=chain.GENESIS_HEADER.pack(), target=1)
        for _ in warm.mine(warm_req):
            pass

        cluster = await Cluster.create(
            n_miners=1, chunk_size=1 << 20,
            miner_factory=lambda: JaxMiner(scrypt_batch=64, depth=2),
        )
        try:
            from tpuminter.lsp import LspClient
            from tpuminter.protocol import encode_msg

            doomed = await LspClient.connect(
                "127.0.0.1", cluster.coord.port, FAST
            )
            doomed.write(encode_msg(Request(
                job_id=1, mode=PowMode.SCRYPT, lower=0, upper=(1 << 20) - 1,
                header=chain.GENESIS_HEADER.pack(), target=1,
            )))
            await asyncio.sleep(1.0)  # miner is now pipelining batches
            req_b = Request(job_id=2, mode=PowMode.MIN, lower=0, upper=500,
                            data=b"after pipelined cancel")
            submit_b = asyncio.ensure_future(
                submit("127.0.0.1", cluster.coord.port, req_b, params=FAST)
            )
            await asyncio.sleep(0.2)
            assert not submit_b.done()  # queued behind A's in-flight chunk
            t0 = _time.monotonic()
            await doomed.close()  # A dies → Cancel lands mid-pipeline
            result = await asyncio.wait_for(submit_b, 30.0)
            print(f"pipelined-cancel: death→B-complete "
                  f"{_time.monotonic() - t0:.2f}s")
            assert (result.hash_value, result.nonce) == brute_min(
                b"after pipelined cancel", 0, 500
            )
        finally:
            await cluster.close()

    run(scenario(), timeout=120)


# ---------------------------------------------------------------------------
# binary-codec interop (ISSUE 4 acceptance): mixed-version peers share a
# wire with no flag day — codec choice is negotiated per connection and
# degrades to JSON whenever either side doesn't speak binary
# ---------------------------------------------------------------------------

def test_binary_coordinator_interops_with_json_only_worker():
    """A binary-codec coordinator (shipping default) serving a worker
    pinned to JSON (the pre-binary peer stand-in): no binary payload
    may reach the worker, and the answer is still brute-force exact."""

    async def scenario():
        cluster = await Cluster.create(n_miners=0, chunk_size=1024)
        task = asyncio.ensure_future(run_miner(
            "127.0.0.1", cluster.coord.port, CpuMiner(), params=FAST,
            binary=False,
        ))
        cluster.miner_tasks.append(task)
        await asyncio.sleep(0.05)
        try:
            req = Request(job_id=4, mode=PowMode.MIN, lower=0, upper=6000,
                          data=b"json-only worker")
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST),
                30.0,
            )
            assert (result.hash_value, result.nonce) == brute_min(
                b"json-only worker", 0, 6000
            )
            # the negotiation really resolved to JSON for this conn
            assert all(
                not m.binary for m in cluster.coord._miners.values()
            )
        finally:
            await cluster.close()

    run(scenario())


def test_json_coordinator_interops_with_binary_capable_worker():
    """The other direction: an old (JSON-pinned) coordinator serving a
    modern worker that ADVERTISES binary. The coordinator never sends a
    binary payload, so the worker never flips its own send side — the
    advertisement alone must not break anything."""

    async def scenario():
        cluster = await Cluster.create(
            n_miners=1, chunk_size=1024, binary_codec=False
        )
        try:
            req = Request(job_id=5, mode=PowMode.MIN, lower=0, upper=6000,
                          data=b"json-only coordinator")
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST),
                30.0,
            )
            assert (result.hash_value, result.nonce) == brute_min(
                b"json-only coordinator", 0, 6000
            )
            assert all(
                not m.binary for m in cluster.coord._miners.values()
            )
        finally:
            await cluster.close()

    run(scenario())


def test_binary_both_ends_negotiates_and_answers_exactly():
    """Shipping defaults on both ends: the Join advertisement flips the
    coordinator, the coordinator's first binary Assign flips the
    worker, binary traffic actually flows, and the fold is still
    brute-force exact (the codec can never change meaning)."""
    from tpuminter import protocol

    async def scenario():
        before = dict(protocol.codec_stats)
        cluster = await Cluster.create(n_miners=2, chunk_size=1024)
        try:
            req = Request(job_id=6, mode=PowMode.MIN, lower=0, upper=9000,
                          data=b"binary both ends")
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST),
                30.0,
            )
            assert (result.hash_value, result.nonce) == brute_min(
                b"binary both ends", 0, 9000
            )
            assert all(m.binary for m in cluster.coord._miners.values())
            # both directions used the fast path: binary messages were
            # encoded AND decoded in this process (assigns out, results
            # back)
            assert protocol.codec_stats["binary_encoded"] > before[
                "binary_encoded"
            ]
            assert protocol.codec_stats["binary_decoded"] > before[
                "binary_decoded"
            ]
        finally:
            await cluster.close()

    run(scenario())


def test_hedge_loser_with_pipelined_chunks_releases_them_all():
    """Pipelining × hedging regression: the hedge-loser Cancel is
    job-scoped, so a loser holding OTHER chunks of the same job
    (depth-2 pipeline) silently abandons them — the coordinator must
    release and requeue every one of them at settlement, or the job
    could only finish via a second hedge cycle (or never). Pinned by
    the hedge count: exactly ONE hedge suffices, with the loser's
    other chunk completing through a normal requeue."""
    import time as _time

    from tpuminter.worker import Miner

    class StallMiner(Miner):
        backend = "stall"
        lanes = 1

        def mine(self, request):
            while True:
                _time.sleep(0.05)
                yield None

    async def scenario():
        cluster = await Cluster.create(
            n_miners=0, chunk_size=3000, hedge_after=0.5
        )
        # join order pins breadth-first dispatch: stall takes chunks A
        # and C (depth 2), cpu takes B
        await cluster.add_miner(StallMiner())
        await cluster.add_miner(CpuMiner(batch=256))
        try:
            req = Request(job_id=1, mode=PowMode.MIN, lower=0, upper=8999,
                          data=b"hedge pipeline leak")
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST),
                30.0,
            )
            assert (result.hash_value, result.nonce) == brute_min(
                b"hedge pipeline leak", 0, 8999
            )
            # one hedge rescued the stalled HEAD chunk; the loser's
            # second pipelined chunk was requeued at settlement — a
            # second hedge (the pre-fix self-heal path) means the
            # release leaked
            assert cluster.coord.stats["chunks_hedged"] == 1, (
                cluster.coord.stats
            )
            assert cluster.coord.stats["chunks_requeued"] >= 1
        finally:
            await cluster.close()

    run(scenario())


# ---------------------------------------------------------------------------
# the answered-job hold: a worker whose own found TARGET/SCRYPT Result
# passes the coordinator's check mines no further chunk of that job
# ---------------------------------------------------------------------------

class RecordingCpuMiner(CpuMiner):
    """A CPU miner that records the ``(job_id, lower, upper)`` of every
    chunk it starts to mine."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self.mined = []

    def mine(self, request):
        self.mined.append((request.job_id, request.lower, request.upper))
        yield from super().mine(request)


class OnceWrongMiner(RecordingCpuMiner):
    """Claims a winner that is no winner for its first chunk, then
    mines honestly."""

    def mine(self, request):
        if self.mined:
            yield from super().mine(request)
            return
        self.mined.append((request.job_id, request.lower, request.upper))
        yield Result(
            request.job_id, request.mode, request.lower, hash_value=0,
            found=True, searched=1, chunk_id=request.chunk_id,
        )


def _genesis_job(job_id: int, chunk: int) -> Request:
    """A TARGET job over two chunks of ``chunk`` nonces; its one winner,
    the genesis nonce, lies in the first."""
    g = chain.GENESIS_HEADER.nonce
    return Request(
        job_id=job_id, mode=PowMode.TARGET, lower=g - chunk // 2,
        upper=g + chunk + chunk // 2 - 1, header=chain.GENESIS_HEADER.pack(),
        target=chain.bits_to_target(0x1D00FFFF),
    )


def test_wrong_winner_holds_nothing():
    """A miner's claimed winner that fails the coordinator's check
    holds nothing on the worker: the coordinator rejects and requeues
    the chunk, the pipelined chunk is mined, and the job still gets its
    true answer."""
    chunk = 1024
    req = _genesis_job(1, chunk)
    miner = OnceWrongMiner()

    async def scenario():
        cluster = await Cluster.create(n_miners=0, chunk_size=chunk)
        await cluster.add_miner(miner)
        try:
            result = await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST),
                30.0,
            )
            assert cluster.coord.stats["results_rejected"] == 1
            return result
        finally:
            await cluster.close()

    result = run(scenario())
    assert (result.found, result.nonce) == (True, chain.GENESIS_HEADER.nonce)
    first = (1, req.lower, req.lower + chunk - 1)
    second = (1, req.lower + chunk, req.upper)
    assert miner.mined == [first, second, first]


@pytest.mark.parametrize("trigger", ["own_cancel", "next_job", "other_cancel"])
def test_held_chunk_dropped_by_cancel_released_by_other_message(trigger):
    """A fake coordinator pipelines two chunks of a TARGET job whose
    winner is in the first. The worker answers the first and holds the
    second. ``own_cancel``: the job's Cancel drops it unmined, with no
    Result. Any other message (``next_job``: the next job's Setup and
    Assign; ``other_cancel``: another job's Cancel) releases it first:
    it is mined, and answered, before that message is handled."""
    from tpuminter.lsp import LspServer
    from tpuminter.protocol import (
        Assign, Cancel, Join, Setup, decode_msg, encode_msg,
    )

    g = chain.GENESIS_HEADER.nonce
    job1 = Request(
        job_id=1, mode=PowMode.TARGET, lower=g - 10, upper=g + 40,
        header=chain.GENESIS_HEADER.pack(),
        target=chain.bits_to_target(0x1D00FFFF),
    )
    job2 = Request(job_id=2, mode=PowMode.MIN, lower=0, upper=99,
                   data=b"after the hold")
    miner = RecordingCpuMiner()

    async def scenario():
        server = await LspServer.create(params=FAST)
        worker = asyncio.ensure_future(
            run_miner("127.0.0.1", server.port, miner, params=FAST)
        )
        try:
            conn, raw = await asyncio.wait_for(server.read(), 10.0)
            assert isinstance(decode_msg(raw), Join)

            async def next_result(timeout=10.0):
                _, raw = await asyncio.wait_for(server.read(), timeout)
                return decode_msg(raw)

            for m in (Setup(job1), Assign(1, 11, g - 10, g + 10),
                      Assign(1, 12, g + 11, g + 40)):
                server.write(conn, encode_msg(m))
            won = await next_result()
            assert (won.chunk_id, won.found, won.nonce) == (11, True, g)
            with pytest.raises(asyncio.TimeoutError):
                await next_result(timeout=0.5)  # chunk 12 is held
            assert miner.mined == [(1, g - 10, g + 10)]
            if trigger == "own_cancel":
                server.write(conn, encode_msg(Cancel(1)))
            elif trigger == "other_cancel":
                server.write(conn, encode_msg(Cancel(9)))
            for m in (Setup(job2), Assign(2, 13, 0, 99)):
                server.write(conn, encode_msg(m))
            got = [await next_result()]
            if trigger != "own_cancel":
                got.append(await next_result())
            return got
        finally:
            worker.cancel()
            await asyncio.gather(worker, return_exceptions=True)
            await server.close()

    got = run(scenario())
    want2 = brute_min(job2.data, 0, 99)
    assert (got[-1].chunk_id, got[-1].hash_value, got[-1].nonce) == (
        13, *want2
    )
    if trigger == "own_cancel":
        assert miner.mined == [(1, g - 10, g + 10), (2, 0, 99)]
    else:
        assert (got[0].chunk_id, got[0].found) == (12, False)
        assert miner.mined == [
            (1, g - 10, g + 10), (1, g + 11, g + 40), (2, 0, 99)
        ]


@pytest.mark.parametrize("mode", [PowMode.MIN, PowMode.TARGET, PowMode.SCRYPT])
def test_jobs_without_a_found_winner_mine_every_chunk(mode):
    """A MIN job, whose Results are always ``found``, and targeted jobs
    that no nonce wins hold nothing: every chunk is mined, once."""
    chunk, upper = 32, 1023  # SCRYPT carves 512 at the least
    if mode == PowMode.MIN:
        req = Request(job_id=1, mode=mode, lower=0, upper=upper,
                      data=b"nothing held")
    else:
        req = Request(job_id=1, mode=mode, lower=0, upper=upper,
                      header=chain.GENESIS_HEADER.pack(), target=1)
    miner = RecordingCpuMiner(batch=8)

    async def scenario():
        cluster = await Cluster.create(n_miners=0, chunk_size=chunk)
        await cluster.add_miner(miner)
        try:
            return await asyncio.wait_for(
                submit("127.0.0.1", cluster.coord.port, req, params=FAST),
                30.0,
            )
        finally:
            await cluster.close()

    result = run(scenario())
    mined = sorted(miner.mined)
    assert len(mined) >= 2  # a pipelined chunk behind each Result
    assert [lo for _, lo, _ in mined] == [0] + [hi + 1 for _, _, hi in mined[:-1]]
    assert mined[-1][2] == upper
    if mode == PowMode.MIN:
        assert (result.hash_value, result.nonce) == brute_min(
            req.data, 0, upper
        )
    else:
        assert not result.found
