"""The worker CLI's device miner runs in a child process
(``tpuminter.miner_proc``) so that a mining step that holds the GIL —
lowering a Pallas kernel does, for seconds — cannot starve the worker's
LSP heartbeats. The hazard is shown first: the same step in the
worker's own process gets it declared dead at the CLI's loss horizon.
"""

import asyncio
import contextlib
import dataclasses
import os
import signal
import socket
import struct
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from tpuminter import chain
from tpuminter.client import submit
from tpuminter.lsp.params import FAST
from tpuminter.miner_proc import ProcessMiner
from tpuminter.protocol import PowMode, Request
from tpuminter.worker import CpuMiner, run_miner
from tpuminter.workloads import hashcore as hc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gil_hold(seconds: float) -> None:
    """Hold the GIL for about ``seconds`` in one C call: ``sum`` over a
    range never reaches the interpreter's switch check."""
    n = 1 << 20
    t0 = time.perf_counter()
    sum(range(n))
    per = (time.perf_counter() - t0) / n
    sum(range(int(seconds / per)))


class GilHogMiner(CpuMiner):
    """A CPU miner whose first step holds the GIL like a first compile."""

    def mine(self, request):
        _gil_hold(4.0)
        yield None
        yield from super().mine(request)


def _toy(job_id: int, upper: int) -> Request:
    return Request(job_id, PowMode.MIN, 0, upper, data=b"miner-proc")


def _drain(gen):
    for item in gen:
        if item is not None:
            return item
    raise AssertionError("no Result")


@contextlib.contextmanager
def _coordinator_cli(*args: str):
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "tpuminter.coordinator", str(port), *args],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        for line in proc.stdout:
            if "listening on port" in line:
                break
        yield port
    finally:
        proc.terminate()
        proc.communicate(timeout=20)


@pytest.fixture
def coordinator():
    """A coordinator CLI at the worker's default loss horizon, in its
    own process so the hog cannot stall its epoch clock too."""
    with _coordinator_cli() as port:
        yield port


@pytest.mark.parametrize("isolated", [False, True])
def test_gil_holding_step_and_the_loss_horizon(coordinator, isolated):
    """In the worker's process a 4 s GIL hold outlasts the 1.25 s
    horizon and the session dies; behind a ProcessMiner the same step
    leaves the session alive and the job answers exactly."""
    miner = ProcessMiner(GilHogMiner) if isolated else GilHogMiner()
    req = _toy(1, 3000)
    want = min((chain.toy_hash(req.data, n), n) for n in range(3001))

    async def scenario():
        worker = asyncio.ensure_future(
            run_miner("127.0.0.1", coordinator, miner, params=FAST)
        )
        await asyncio.sleep(0.3)
        job = asyncio.ensure_future(
            submit("127.0.0.1", coordinator, req, params=FAST)
        )
        await asyncio.wait(
            {worker, job}, timeout=60, return_when=asyncio.FIRST_COMPLETED
        )
        if not isolated:  # the client in this process starves too
            await asyncio.wait({worker}, timeout=20)
        answered = job.done() and job.exception() is None
        outcome = (worker.done(), job.result() if answered else None)
        for t in (worker, job):
            t.cancel()
        await asyncio.gather(worker, job, return_exceptions=True)
        return outcome

    try:
        worker_ended, result = asyncio.run(scenario())
    finally:
        if isolated:
            miner.shutdown()
    if isolated:
        assert not worker_ended
        assert (result.hash_value, result.nonce) == want
    else:
        assert worker_ended and result is None  # declared dead mid-hold


def test_abandoned_job_then_exact_answers():
    """A job abandoned mid-mine (Cancel) is stopped in the child; the
    next mining job and a workload chunk answer as in-process miners
    do."""
    pm = ProcessMiner(CpuMiner, batch=64)
    try:
        assert (pm.backend, pm.follower) == ("cpu", False)
        gen = pm.mine(_toy(1, 1 << 20))
        assert next(gen) is None
        del gen  # abandoned: the child gets a cancel
        req = _toy(2, 999)
        assert _drain(pm.mine(req)) == _drain(CpuMiner().mine(req))
        work = Request(
            job_id=3, mode=PowMode.MIN, lower=0, upper=299,
            data=hc.pack_params("fmin", seed=7, threshold=0, k=3),
            chunk_id=1, workload="hashcore",
        )
        assert _drain(pm.compute(work)) == _drain(CpuMiner().compute(work))
    finally:
        pm.shutdown()


class FailingMiner(CpuMiner):
    def mine(self, request):
        if request.job_id == 1:
            raise ValueError("no such kernel")
        return super().mine(request)


def _refuse():
    raise ValueError("no chip here")


def test_errors_surface_in_the_worker():
    with pytest.raises(RuntimeError, match="no chip here"):
        ProcessMiner(_refuse)
    pm = ProcessMiner(FailingMiner)
    try:
        with pytest.raises(RuntimeError, match="no such kernel"):
            _drain(pm.mine(_toy(1, 10)))
        req = _toy(2, 10)  # the child still serves
        assert _drain(pm.mine(req)) == _drain(CpuMiner().mine(req))
    finally:
        pm.shutdown()


def test_child_dies_with_a_killed_worker():
    """A worker killed outright must not leave its miner holding the
    chip."""
    script = textwrap.dedent("""
        import sys, time
        from tpuminter.miner_proc import ProcessMiner
        from tpuminter.worker import CpuMiner
        pm = ProcessMiner(CpuMiner)
        print(pm._proc.pid, flush=True)
        time.sleep(120)
    """)
    parent = subprocess.Popen(
        [sys.executable, "-c", script], cwd=ROOT,
        stdout=subprocess.PIPE, text=True,
    )
    child = int(parent.stdout.readline())
    parent.send_signal(signal.SIGKILL)
    parent.wait(10)
    deadline = time.time() + 20
    while time.time() < deadline:
        try:
            os.kill(child, 0)
        except ProcessLookupError:
            return
        time.sleep(0.1)
    os.kill(child, signal.SIGKILL)
    pytest.fail("the miner process outlived its worker")


def test_close_keeps_the_child_serving():
    """``run_miner`` forwards ``close`` when a session ends; the child
    keeps serving (a ``--reconnect`` worker mines again on its next
    session) until ``shutdown``."""
    pm = ProcessMiner(CpuMiner)
    try:
        pm.close()
        req = _toy(4, 50)
        assert _drain(pm.mine(req)) == _drain(CpuMiner().mine(req))
    finally:
        pm.shutdown()
    assert not pm._proc.is_alive()


class SlowMiner(CpuMiner):
    """A CPU miner that sleeps ``pause`` seconds in each step but the
    last and logs ``job chunk time`` (the system-wide monotonic clock)
    to ``log`` as each step ends."""

    def __init__(self, log: str, pause: float):
        super().__init__(batch=64)
        self.log, self.pause = log, pause

    def mine(self, request):
        for item in super().mine(request):
            if item is None:
                time.sleep(self.pause)
            with open(self.log, "a") as f:
                f.write(f"{request.job_id} {request.chunk_id} "
                        f"{time.monotonic()}\n")
            yield item


def _step_times(log: str, job_id: int) -> list:
    with open(log) as f:
        rows = [line.split() for line in f]
    return [float(t) for job, _, t in rows if int(job) == job_id]


@pytest.mark.parametrize("raced", [False, True])
def test_cancel_from_another_thread(tmp_path, raced):
    """``cancel()`` from another thread while ``next(gen)`` waits in
    ``recv``: the child runs at most one more step and the generator
    ends with no Result. ``raced``: the caller closes the generator
    after a step that crossed the cancel, as the role loop does; the job
    then owes one terminal, however many cancels were sent. Either way
    the next job and a workload chunk answer as in-process miners do."""
    log = str(tmp_path / "steps")
    pm = ProcessMiner(SlowMiner, log, pause=0.1)
    sent = []

    def cancel():
        sent.append(time.monotonic())
        pm.cancel()

    try:
        gen = pm.mine(_toy(1, 1 << 14))  # 255 steps of 0.1 s uncancelled
        assert next(gen) is None
        timer = threading.Timer(0.05, cancel)
        timer.start()
        if raced:
            timer.join()
            gen.close()  # before the child's terminal came back
            assert pm._owed == 1
        else:
            assert all(item is None for item in gen)
            timer.join()
            assert pm._owed == 0
        pm.cancel()  # a second cancel, with no job running: dropped
        req = _toy(2, 300)
        assert _drain(pm.mine(req)) == _drain(CpuMiner().mine(req))
        assert pm._owed == 0
        work = Request(
            job_id=3, mode=PowMode.MIN, lower=0, upper=299,
            data=hc.pack_params("fmin", seed=7, threshold=0, k=3),
            chunk_id=1, workload="hashcore",
        )
        assert _drain(pm.compute(work)) == _drain(CpuMiner().compute(work))
    finally:
        pm.shutdown()
    assert len([t for t in _step_times(log, 1) if t > sent[0]]) <= 1


class RecordingProcessMiner(ProcessMiner):
    """Records when the role loop forwards each cancel."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.cancels = []

    def cancel(self) -> None:
        self.cancels.append(time.monotonic())
        super().cancel()


def _early_winner(job_id: int, chunk: int, batch: int = 64, at: int = 0):
    """A TARGET job over two chunks whose one winner, the job's least
    hash, lies in the first batch of chunk ``at`` (0 or 1)."""
    base = chain.GENESIS_HEADER
    for dt in range(1, 1000):
        header = dataclasses.replace(base, timestamp=base.timestamp + dt).pack()
        hashes = [
            chain.hash_to_int(chain.dsha256(header[:76] + struct.pack("<I", n)))
            for n in range(2 * chunk)
        ]
        w = min(range(2 * chunk), key=hashes.__getitem__)
        if at * chunk <= w < at * chunk + batch:
            req = Request(job_id, PowMode.TARGET, 0, 2 * chunk - 1,
                          header=header, target=hashes[w])
            return req, w
    raise AssertionError("no header with an early winner")


def _step_chunks(log: str, job_id: int) -> set:
    with open(log) as f:
        rows = [line.split() for line in f]
    return {int(chunk) for job, chunk, _ in rows if int(job) == job_id}


@pytest.mark.parametrize("next_job", [False, True])
def test_cancel_reaches_the_child_mid_step(tmp_path, next_job):
    """The role loop forwards a Cancel for the chunk being mined while
    its step runs: the child runs at most one more step of it, also
    when no further job ever arrives. The job is answered by another
    worker: this one mines the first chunk, a fast helper that joins
    later takes the second, which holds the winner (one chunk a worker
    at a time). ``next_job``: the next job's Setup and Assign, queued
    right behind the Cancel, are still handled."""
    log = str(tmp_path / "steps")
    chunk = 1024  # 16 steps a chunk
    req1, winner = _early_winner(1, chunk, at=1)
    req2 = _toy(2, 255)
    want2 = min((chain.toy_hash(req2.data, n), n) for n in range(256))
    miner = RecordingProcessMiner(SlowMiner, log, pause=0.2)

    async def scenario(port):
        worker = asyncio.ensure_future(
            run_miner("127.0.0.1", port, miner, params=FAST)
        )
        await asyncio.sleep(0.3)
        job1 = asyncio.ensure_future(
            submit("127.0.0.1", port, req1, params=FAST)
        )
        while not os.path.exists(log):  # this worker mines chunk 1
            await asyncio.sleep(0.05)
        helper = asyncio.ensure_future(
            run_miner("127.0.0.1", port, CpuMiner(), params=FAST)
        )
        r1 = await asyncio.wait_for(job1, 30)
        r2 = None
        if next_job:
            r2 = await asyncio.wait_for(
                submit("127.0.0.1", port, req2, params=FAST), 30
            )
        await asyncio.sleep(1.0)  # the child would mine on meanwhile
        assert not worker.done()
        for t in (worker, helper):
            t.cancel()
        await asyncio.gather(worker, helper, return_exceptions=True)
        return r1, r2

    try:
        with _coordinator_cli(
            "--chunk-size", str(chunk), "--pipeline-depth", "1"
        ) as port:
            r1, r2 = asyncio.run(scenario(port))
    finally:
        miner.shutdown()
    assert (r1.found, r1.nonce) == (True, winner)
    assert len(miner.cancels) == 1
    assert len([t for t in _step_times(log, 1) if t > miner.cancels[0]]) <= 1
    if next_job:
        assert (r2.hash_value, r2.nonce) == want2


def test_answered_job_holds_its_queued_chunk(tmp_path):
    """The worker's first chunk answers the job; the second, pipelined
    behind it, is held until the job's Cancel drops it, so it never
    reaches the child and no cancel is sent there. The answer is the
    same, and the next job on the same worker is exact."""
    log = str(tmp_path / "steps")
    chunk = 1024
    req1, winner = _early_winner(1, chunk)
    req2 = _toy(2, 255)
    want2 = min((chain.toy_hash(req2.data, n), n) for n in range(256))
    miner = RecordingProcessMiner(SlowMiner, log, pause=0.2)

    async def scenario(port):
        worker = asyncio.ensure_future(
            run_miner("127.0.0.1", port, miner, params=FAST)
        )
        await asyncio.sleep(0.3)
        r1 = await asyncio.wait_for(
            submit("127.0.0.1", port, req1, params=FAST), 30
        )
        r2 = await asyncio.wait_for(
            submit("127.0.0.1", port, req2, params=FAST), 30
        )
        await asyncio.sleep(1.0)  # a released chunk would be mined now
        assert not worker.done()
        worker.cancel()
        await asyncio.gather(worker, return_exceptions=True)
        return r1, r2

    try:
        with _coordinator_cli("--chunk-size", str(chunk)) as port:
            r1, r2 = asyncio.run(scenario(port))
    finally:
        miner.shutdown()
    assert (r1.found, r1.nonce) == (True, winner)
    assert (r2.hash_value, r2.nonce) == want2
    assert len(_step_chunks(log, 1)) == 1  # the winner's chunk alone
    assert miner.cancels == []


def test_session_lost_mid_step(tmp_path):
    """The coordinator dies while a step runs: the role loop cancels the
    chunk and returns only once that step has, so the miner's pipe is
    clean for the next session's first job."""
    log = str(tmp_path / "steps")
    miner = RecordingProcessMiner(SlowMiner, log, pause=0.2)

    async def scenario():
        with _coordinator_cli() as port:
            worker = asyncio.ensure_future(
                run_miner("127.0.0.1", port, miner, params=FAST)
            )
            await asyncio.sleep(0.3)
            job = asyncio.ensure_future(
                submit("127.0.0.1", port, _toy(1, 1 << 14), params=FAST)
            )
            while not os.path.exists(log):
                await asyncio.sleep(0.05)
        # the coordinator is gone: the worker declares it lost mid-chunk
        await asyncio.wait_for(worker, 30)
        job.cancel()
        await asyncio.gather(job, return_exceptions=True)

    try:
        asyncio.run(scenario())
        assert len(miner.cancels) == 1
        req = _toy(2, 300)
        assert _drain(miner.mine(req)) == _drain(CpuMiner().mine(req))
    finally:
        miner.shutdown()
    assert len([t for t in _step_times(log, 1) if t > miner.cancels[0]]) <= 1
