"""The pod candidate sweep in a device trace, for the readers of
``benchmark/metrics/pod_*.py``.

``PodMiner`` runs each pod-wide sweep as one program,
``jit_pod_candidate_sweep`` on every chip's ``XLA Modules`` line. Inside
it each chip runs the candidate kernel once a stripe, one op event named
``pallas_search_candidates.<n>`` on its ``XLA Ops`` line, and then the
ICI or-reduce that decides whether the next stripe runs.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from traces import MODULES_LINE

PROGRAM = "jit_pod_candidate_sweep"
KERNEL = "pallas_search_candidates"


def pod_runs(trace) -> Dict[str, List[Tuple[float, List[float]]]]:
    """Per chip, each run of the pod sweep that holds at least one kernel
    op: its seconds, and the seconds of each kernel op inside it. A run
    the trace cut before its first kernel op is left out. Empty where
    the trace has no pod program."""
    out: Dict[str, List[Tuple[float, List[float]]]] = {}
    if trace is None:
        return out
    for device, lines in trace.devices.items():
        if MODULES_LINE not in lines:
            continue
        kernels = [(s, e) for s, e, name in trace.op_events(device)
                   if name.split(".")[0] == KERNEL]
        runs = []
        for start, end, name in trace.programs(device):
            if name != PROGRAM:
                continue
            inside = [e - s for s, e in kernels if start <= (s + e) / 2 <= end]
            if inside:
                runs.append((end - start, inside))
        if runs:
            out[device] = runs
    return out
