"""A device miner in a process of its own.

Lowering a Pallas SHA kernel holds the GIL in C++ for a second or more
at a time. In the worker's own process that starves the asyncio loop
that answers LSP heartbeats: each loop turn needs the GIL several times
(wake from epoll, run the epoch timer, send), so a 9 s lowering left
the loop 5 s behind on the CPU and the coordinator declared the worker
dead on its first job (PR 21). :class:`ProcessMiner` runs the miner in a
spawned child: the worker's process never imports JAX, and its LSP
session runs on a GIL no compiler touches.

The pipe carries tuples. Parent → child: ``("mine" | "compute",
request, progress)`` starts a job, ``("cancel",)`` abandons it,
``("close",)`` forwards :meth:`Miner.close`, ``("stop",)`` ends the
child. Child → parent, per job: ``("step",)`` at each yield point,
``("progress", hw, nonce, hash)`` from the miner's ``progress_cb``, and
exactly one terminal ``("result", r)``, ``("end",)`` or ``("error",
traceback)``.
"""

from __future__ import annotations

import contextlib
import ctypes
import logging
import multiprocessing
import os
import signal
import threading
import traceback
from typing import Callable, Iterator, Optional

from tpuminter.protocol import PowMode, Request, Result
from tpuminter.spans import AWAIT_CHUNK, CANCEL, WINNER, span
from tpuminter.worker import Miner, ProfiledMiner, _build_miner

__all__ = ["ProcessMiner", "device_miner"]

log = logging.getLogger("tpuminter.miner_proc")

_TERMINAL = ("result", "end", "error")
_PR_SET_PDEATHSIG = 1


class ProcessMiner(Miner):
    """Runs ``factory(*args, **kwargs)`` in a spawned child and mines
    through it. ``factory`` must be picklable (a module-level callable).
    It may return None: the child did its whole job at start-up (a pod
    follower) and :attr:`follower` is then True; :meth:`join` waits for
    it."""

    def __init__(self, factory: Callable[..., Optional[Miner]], *args, **kwargs):
        ctx = multiprocessing.get_context("spawn")
        self._conn, child = ctx.Pipe()
        self._proc = ctx.Process(
            target=_serve, args=(child, factory, args, kwargs),
            name="tpuminter-miner", daemon=True,
        )
        self._proc.start()
        child.close()
        #: one sender at a time: :meth:`cancel` sends from the role
        #: loop's thread while a job's step may send from the executor's
        self._send_lock = threading.Lock()
        #: terminal messages still owed by jobs abandoned mid-mine
        self._owed = 0
        hello = self._recv()
        if hello[0] == "error":
            self.shutdown()
            raise RuntimeError(f"miner process failed to start:\n{hello[1]}")
        self.follower = hello[0] == "follower"
        if not self.follower:
            _, self.backend, self.lanes, self.span = hello

    def _recv(self) -> tuple:
        try:
            return self._conn.recv()
        except EOFError:
            self._proc.join(5.0)
            raise RuntimeError(
                f"miner process exited (code {self._proc.exitcode})"
            ) from None

    def mine(self, request: Request) -> Iterator[Optional[Result]]:
        return self._job("mine", request)

    def compute(self, request: Request) -> Iterator[Optional[Result]]:
        return self._job("compute", request)

    def _job(self, kind: str, request: Request) -> Iterator[Optional[Result]]:
        while self._owed:
            if self._recv()[0] in _TERMINAL:
                self._owed -= 1
        self._send((kind, request, self.progress_cb is not None))
        done = False
        try:
            while True:
                msg = self._recv()
                tag = msg[0]
                if tag == "step":
                    yield None
                elif tag == "progress":
                    cb = self.progress_cb
                    if cb is not None:
                        cb(*msg[1:])
                elif tag == "result":
                    done = True
                    yield msg[1]
                    return
                elif tag == "end":
                    done = True
                    return
                else:
                    done = True
                    raise RuntimeError(f"miner process:\n{msg[1]}")
        finally:
            if not done:
                # closed before its terminal: the child stops at its
                # next yield point, and the next job first reads this
                # one's terminal. After a cancel() whose step raced it
                # this cancel is a second one, which the child drops
                # between jobs, so the job still owes one terminal.
                self._send(("cancel",))
                self._owed += 1

    def _send(self, cmd: tuple) -> None:
        with self._send_lock:
            self._conn.send(cmd)

    def cancel(self) -> None:
        """Send the child a cancel now, from any thread: a step blocked
        in ``recv`` on the executor's thread then ends with ``("end",)``,
        or with a ``("step",)`` that crossed it (the pipe's directions
        are separate, so sending does not disturb that ``recv``). The
        job's generator is still closed after that step; a cancel that
        finds no job running is dropped by the child."""
        self._send(("cancel",))

    def close(self) -> None:
        """Forward :meth:`Miner.close` (a trace flush, a pod's follower
        release); the child keeps serving. :meth:`shutdown` ends it."""
        if self._proc.is_alive():
            self._send(("close",))

    def join(self) -> None:
        self._proc.join()

    def shutdown(self, grace: float = 10.0) -> None:
        if self._proc.is_alive():
            try:
                self._send(("stop",))
            except OSError:
                pass
            self._proc.join(grace)
        if self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(grace)
        self._conn.close()


def _die_with_parent() -> None:
    """A worker killed outright must not leave its child holding the
    chip: ask Linux to kill this process when the parent dies. SIGKILL,
    because jax.distributed catches SIGTERM (preemption notice)."""
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        libc.prctl(_PR_SET_PDEATHSIG, signal.SIGKILL)
    except (OSError, AttributeError):
        return
    if os.getppid() == 1:  # the parent died before the prctl
        os._exit(1)


def _serve(conn, factory, args, kwargs) -> None:
    _die_with_parent()
    try:
        miner = factory(*args, **kwargs)
    except BaseException:
        conn.send(("error", traceback.format_exc()))
        return
    if miner is None:
        conn.send(("follower",))
        return
    conn.send(("ready", miner.backend, miner.lanes, miner.span))
    while True:
        try:
            with span(AWAIT_CHUNK):
                cmd = conn.recv()
        except EOFError:
            return
        if cmd[0] == "stop":
            return
        if cmd[0] == "close":
            _close(miner)
        elif cmd[0] in ("mine", "compute"):
            if not _run_job(conn, miner, *cmd):
                return
        # a cancel that crossed its job's terminal on the pipe: nothing
        # is left to abandon


def _close(miner: Miner) -> None:
    closer = getattr(miner, "close", None)
    if callable(closer):
        closer()


def _run_job(conn, miner: Miner, kind: str, request: Request, progress: bool) -> bool:
    """Mine one job, streaming its steps; False when the parent asked
    this process to stop (or is gone) mid-job."""
    miner.progress_cb = (
        (lambda hw, n, h: conn.send(("progress", hw, n, h)))
        if progress else None
    )
    gen = None
    try:
        gen = miner.compute(request) if kind == "compute" else miner.mine(request)
        for item in gen:
            if item is not None:
                with _result_span(request, item):
                    conn.send(("result", item))
                return True
            if conn.poll():
                try:
                    cmd = conn.recv()
                except EOFError:
                    return False
                # a cancel, or the session ending (close) or the
                # worker ending (stop): each abandons the job
                if cmd[0] == "cancel":
                    with span(CANCEL, job=request.job_id, chunk=request.chunk_id):
                        gen.close()
                        conn.send(("end",))
                    return True
                gen.close()
                if cmd[0] == "close":
                    _close(miner)
                conn.send(("end",))
                return cmd[0] != "stop"
            conn.send(("step",))
        conn.send(("end",))
    except Exception:
        conn.send(("error", traceback.format_exc()))
    finally:
        if gen is not None:
            gen.close()
    return True


def _result_span(request: Request, item):
    """A winner span around sending a Result that answers its whole
    job (a found TARGET or SCRYPT winner; every MIN chunk is ``found``
    and answers nothing), else no span."""
    if isinstance(item, Result) and item.found and item.mode != PowMode.MIN:
        return span(WINNER, job=request.job_id, chunk=request.chunk_id)
    return contextlib.nullcontext()


def device_miner(
    backend: str,
    *,
    dev_lanes: Optional[str] = None,
    profile: Optional[str] = None,
    **build_kwargs,
) -> Optional[Miner]:
    """The worker CLI's device miner (jax/tpu/pod), built in the child.

    Turns on the compile cache, joins a multi-host pod where the
    environment describes one (a follower replays the leader's programs
    here and returns None), and logs the device JAX reports."""
    logging.basicConfig(level=logging.INFO)
    import jax

    from tpuminter.xla_cache import enable_compilation_cache

    log.info("persistent compilation cache: %s", enable_compilation_cache())
    if dev_lanes is not None:
        from tpuminter.workloads import hashcore

        hashcore.set_dev_lanes(dev_lanes)
    spmd_leader = False
    if backend == "pod":
        from tpuminter.parallel import distributed as dist

        if dist.init_from_env():
            if not dist.is_leader():
                from tpuminter.pod_worker import follower_loop

                follower_loop(_build_miner(backend, **build_kwargs))
                return None
            spmd_leader = True
    miner = _build_miner(backend, spmd_leader=spmd_leader, **build_kwargs)
    devices = jax.devices()
    log.info(
        "device: platform=%s kind=%s count=%d", devices[0].platform,
        devices[0].device_kind, len(devices),
    )
    if backend == "pod":
        log.info("pod mesh: %d devices", miner.n_dev)
    if profile:
        miner = ProfiledMiner(miner, profile)
    return miner
