"""Indices that the batched dynamic-header sweep covers per second of its
own device time, in Gnonce/s: the rolled path's kernel rate.

Read from the device trace and the miner child's program spans. Each
window of a rolled sweep is one ``tpuminter.roll`` span, whose
``nonces`` arg counts the valid indices of the window's rows (a chunk
shorter than the window leaves padding rows, which the kernel skips),
then one run of ``jit_extranonce_roll`` and one of
``jit__pallas_batched_candidate_sweep``; the device runs them in the
order the host dispatched them. So the sweeps from the first roll
program that starts after the first roll span on pair with the roll
spans in order. That holds while no roll dispatched before the trace
opened runs on the device after the first roll span, as in the
benchmark, whose trace opens with the miner idle between the warm-up and
the window's first job; a sweep still queued then is left out. Each
chip's last run is left out: the trace's stop can cut it (a cut run
reads a microsecond). Pairs of a chunk that sent a ``tpuminter.winner``
are left out: that sweep stopped at the winner. A roofline share is not
possible: the chip's integer peak has no published source, so the rate
stands in.

``traces.Trace`` keeps no span args, so the spans are read again from
the run's own profiler file: ``<root>/.bench/<cell>/trace``, the one
whose ``window.json`` spans the trace's window, newest first (each run
of a cell replaces its own directory).
"""

import glob
import gzip
import json
import os

#: the checkout the benchmark runs in: ``run.py``'s root
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUNS_DIR = ".bench"
ROLL, WINNER = "tpuminter.roll", "tpuminter.winner"
ROLL_PROGRAM, SWEEP = "jit_extranonce_roll", "jit__pallas_batched_candidate_sweep"


def _trace_file(trace, root):
    dirs = []
    for window in glob.glob(os.path.join(root, RUNS_DIR, "*", "trace", "window.json")):
        with open(window) as fh:
            w = json.load(fh)
        if w["stop"] - w["start"] == trace.window_s:
            dirs.append((os.path.getmtime(window), os.path.dirname(window)))
    for _, d in sorted(dirs, reverse=True):
        files = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*", "*.trace.json.gz")))
        if files:
            return files[-1]
    return None


def _spans(trace, root):
    """The roll and winner spans, with their args, in start order."""
    path = _trace_file(trace, root)
    if path is None:
        return []
    with gzip.open(path, "rt") as fh:
        events = json.load(fh).get("traceEvents", [])
    return sorted((e["ts"] * 1e-6, e["name"], e.get("args", {})) for e in events
                  if e.get("ph") == "X" and "dur" in e and e.get("name") in (ROLL, WINNER))


def _chunk(args):
    return str(args.get("job")), str(args.get("chunk"))


def read(run, root=ROOT):
    if run.trace is None or not run.trace.devices:
        return None
    ours = _spans(run.trace, root)
    rolls = [(start, args) for start, name, args in ours if name == ROLL and "nonces" in args]
    if not rolls:
        return None
    won = {_chunk(args) for _, name, args in ours if name == WINNER}
    nonces = seconds = 0.0
    for device in run.trace.devices:
        programs = run.trace.programs(device)
        first = min((s for s, _, name in programs
                     if name == ROLL_PROGRAM and s >= rolls[0][0]), default=None)
        if first is None:
            continue
        runs = [(s, e) for s, e, name in programs if name == SWEEP and s >= first][:-1]
        for (_, args), (s, e) in zip(rolls, runs):
            if _chunk(args) not in won:
                nonces += int(args["nonces"])
                seconds += e - s
    return nonces / seconds / 1e9 if seconds else None
