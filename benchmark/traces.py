"""Reductions from what a run records to numbers: the profiler's trace
of the device miner, and the miner's compile log.

Both are read as plain files, with no JAX in this process. The trace is
the ``*.trace.json.gz`` that ``jax.profiler`` writes beside its xplane
(Chrome trace events: ``ph == "X"`` with ``ts`` and ``dur`` in
microseconds, process and thread names in ``ph == "M"`` records).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

#: the device line of a TPU trace that holds one event per executed op
OPS_LINE = "XLA Ops"
#: the device line that holds one event per executed program
MODULES_LINE = "XLA Modules"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def covered(intervals: Iterable[Interval]) -> float:
    return sum(hi - lo for lo, hi in union(intervals))


class Trace:
    """One profiler session: device events per device, host events, and
    the window the session covered (seconds, on the trace's clock)."""

    def __init__(self, events: list, window_s: float):
        self.window_s = window_s
        procs, threads = {}, {}
        for e in events:
            if e.get("ph") == "M" and e.get("name") == "process_name":
                procs[e["pid"]] = e["args"]["name"]
            elif e.get("ph") == "M" and e.get("name") == "thread_name":
                threads[(e["pid"], e["tid"])] = e["args"]["name"]
        #: device name -> line name -> [(start_s, end_s, name)]
        self.devices: Dict[str, Dict[str, list]] = {}
        #: host events: [(start_s, end_s, name)]
        self.host: list = []
        for e in events:
            if e.get("ph") != "X" or "dur" not in e:
                continue
            start = e["ts"] * 1e-6
            ev = (start, start + e["dur"] * 1e-6, e.get("name", ""))
            proc = procs.get(e["pid"], "")
            if proc.startswith("/device:") and "CPU" not in proc:
                line = threads.get((e["pid"], e.get("tid")), "")
                self.devices.setdefault(proc, {}).setdefault(line, []).append(ev)
            else:
                self.host.append(ev)

    def op_events(self, device: str) -> list:
        """The device's per-op events (all its lines, if it has no
        per-op line)."""
        lines = self.devices[device]
        if OPS_LINE in lines:
            return lines[OPS_LINE]
        return [ev for evs in lines.values() for ev in evs]

    def module_events(self, device: str) -> list:
        lines = self.devices[device]
        return lines.get(MODULES_LINE) or self.op_events(device)

    def busy_s(self) -> Optional[float]:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.devices:
            return None
        return sum(
            covered((s, e) for s, e, _ in self.op_events(d))
            for d in self.devices
        ) / len(self.devices)

    def programs(self, device: str) -> list:
        """The device's program runs, each named without the hash that
        JAX appends: ``jit_roll(1699...)`` is ``jit_roll``."""
        return [(s, e, name.split("(")[0]) for s, e, name in self.module_events(device)]

    def top_programs(self, n: int = 10) -> List[list]:
        """The programs that took most device time, summed over devices
        and divided by their count."""
        tally: Dict[str, float] = {}
        for d in self.devices:
            for s, e, name in self.programs(d):
                tally[name] = tally.get(name, 0.0) + (e - s)
        k = max(1, len(self.devices))
        top = sorted(tally.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k] for name, t in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest gaps between device ops on the first device, each
        named by the shortest host event that spans its middle."""
        if not self.devices:
            return []
        dev = sorted(self.devices)[0]
        busy = union((s, e) for s, e, _ in self.op_events(dev))
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
        out = []
        for length, lo, hi in sorted(gaps, reverse=True)[:n]:
            mid = (lo + hi) / 2
            spans = [ev for ev in self.host if ev[0] <= mid <= ev[1]]
            name = min(spans, key=lambda ev: ev[1] - ev[0])[2] if spans else "no host event"
            out.append([name, length])
        return out


def load_trace(trace_dir: str) -> Optional[Trace]:
    """The session that the benchmark's trace thread recorded, or None
    where it recorded nothing."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    window = os.path.join(trace_dir, "window.json")
    if not files or not os.path.exists(window):
        return None
    with open(window) as fh:
        w = json.load(fh)
    with gzip.open(files[-1], "rt") as fh:
        events = json.load(fh).get("traceEvents", [])
    return Trace(events, w["stop"] - w["start"])


_COMPILE_LINE = re.compile(
    r"^(?P<t>\d+\.\d+) [A-Z]+:[\w.]+:(?P<what>Finished tracing \+ transforming|"
    r"Finished jaxpr to MLIR module conversion|Finished XLA compilation of)"
    r" (?P<name>\S+).* in (?P<dur>[0-9.]+) sec", re.M)

KINDS = {
    "Finished tracing + transforming": "trace",
    "Finished jaxpr to MLIR module conversion": "lower",
    "Finished XLA compilation of": "compile",
}


def compile_events(log_text: str) -> List[dict]:
    """The spans that JAX's compile log (``JAX_LOG_COMPILES=1``) wrote
    into a log whose lines start with their wall-clock time: each with
    its kind (trace, lower, compile), program name, start and end."""
    out = []
    for m in _COMPILE_LINE.finditer(log_text):
        end, dur = float(m["t"]), float(m["dur"])
        out.append({"kind": KINDS[m["what"]], "name": m["name"],
                    "start": end - dur, "end": end})
    return out


_ARGS_LINE = re.compile(
    r"Compiling jit\((?P<name>[^)]+)\) with global shapes and types \((?P<args>.*?)\)\. ")
_SHAPE = re.compile(r"ShapedArray\(\w+\[([\d,]*)\]")


def program_args(log_text: str) -> Dict[str, List[List[int]]]:
    """The argument shapes with which each program was lowered, from
    JAX's compile log: ``{"_scrypt_step": [[19], [16384], [8]]}``. A
    program is lowered in every process, whether its compiled code then
    comes from the cache or not, so the shapes are those the device
    ran."""
    out = {}
    for m in _ARGS_LINE.finditer(log_text):
        out[m["name"]] = [[int(d) for d in dims.split(",") if d]
                          for dims in _SHAPE.findall(m["args"])]
    return out

