"""The served mining path as child processes: one journaled coordinator
and one worker, started through their command-line entry points.

Each child runs in a process group of its own, so stopping it also
reaches the device miner that the worker spawns, and :meth:`stop` waits
until every process of every group has ended.
"""

from __future__ import annotations

import os
import re
import signal
import socket
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

DEVICE_LINE = r"device: platform=(\S+) kind=(.*?) count=(\d+)"


class ClusterError(Exception):
    pass


def free_udp_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Cluster:
    """Coordinator + one worker; logs, journal and profile under
    ``out_dir``. ``worker_cmd`` is the worker's command line without the
    coordinator address (the address is inserted after the program)."""

    def __init__(self, out_dir: str, worker_cmd: list, env: dict):
        self.out_dir = out_dir
        os.makedirs(out_dir, exist_ok=True)
        self.port = free_udp_port()
        journal = os.path.join(out_dir, "journal.wal")
        if os.path.exists(journal):
            os.remove(journal)
        self.env = env
        self.procs = {}
        self._spawn("coordinator", [
            sys.executable, "-m", "tpuminter.coordinator", str(self.port),
            "--journal", journal,
        ])
        self.wait_for("coordinator", r"coordinator listening on port", 60)
        self._spawn("worker", [
            worker_cmd[0], worker_cmd[1], f"127.0.0.1:{self.port}",
            *worker_cmd[2:],
        ])

    def _spawn(self, role: str, argv: list) -> None:
        log = open(self.log_path(role), "w")
        try:
            proc = subprocess.Popen(
                argv, cwd=ROOT, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
        except BaseException:
            log.close()
            raise
        self.procs[role] = (proc, log)

    def log_path(self, role: str) -> str:
        return os.path.join(self.out_dir, f"{role}.log")

    def log(self, role: str) -> str:
        with open(self.log_path(role), errors="replace") as fh:
            return fh.read()

    def check_alive(self) -> None:
        for role, (proc, _) in self.procs.items():
            if proc.poll() is not None:
                raise ClusterError(
                    f"{role} exited with code {proc.returncode}:\n"
                    + self.log(role)[-3000:]
                )

    def wait_for(self, role: str, pattern: str, timeout: float) -> re.Match:
        """Block until ``role``'s log matches ``pattern``."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            self.check_alive()
            m = re.search(pattern, self.log(role))
            if m:
                return m
            time.sleep(0.1)
        raise ClusterError(f"{role} did not log {pattern!r} in {timeout} s")

    def stop(self) -> None:
        """Stop the worker first (SIGINT: it shuts its device miner down
        cleanly), then the coordinator; kill what does not end in time,
        and wait until no process of either group is left."""
        for role in ("worker", "coordinator"):
            if role not in self.procs:
                continue
            proc, log = self.procs[role]
            if proc.poll() is None:
                proc.send_signal(signal.SIGINT)
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
            _reap_group(proc.pid)
            log.close()


def _reap_group(pgid: int, timeout: float = 30.0) -> None:
    """Signal whatever is left of process group ``pgid`` and wait until
    the group is empty (a device miner must not outlive its worker)."""
    start = time.monotonic()
    sig = signal.SIGTERM
    while time.monotonic() - start < 2 * timeout:
        try:
            os.killpg(pgid, sig)
        except (ProcessLookupError, PermissionError):
            return
        if time.monotonic() - start > timeout:
            sig = signal.SIGKILL
        time.sleep(0.2)
    raise ClusterError(f"process group {pgid} did not end")
