"""Runs of the single-chip candidate sweep that did no nonce work, over
its runs in the traced window, in %.

Read from the device trace. The TARGET miner chains each sweep on the
one dispatched before it (``pallas_search_candidates``' ``stop``
operand): a sweep queued behind a candidate skips the kernel on the
device. Such a run of ``jit_pallas_search_candidates`` on a chip's
``XLA Modules`` line holds no candidate-kernel op on its ``XLA Ops``
line. Each chip's first and last run are left out: the trace's start
and stop cut them, and a cut run can hold no kernel op though it swept.
None where the trace has no run between them.
"""

from traces import MODULES_LINE

PROGRAM = "jit_pallas_search_candidates"
KERNEL = "pallas_search_candidates"


def read(run):
    if run.trace is None:
        return None
    runs = skipped = 0
    for device, lines in run.trace.devices.items():
        if MODULES_LINE not in lines:
            continue
        kernels = [(s, e) for s, e, name in run.trace.op_events(device)
                   if name.split(".")[0] == KERNEL]
        inner = [(start, end) for start, end, name in run.trace.programs(device)
                 if name == PROGRAM][1:-1]
        for start, end in inner:
            runs += 1
            if not any(s < end and e > start for s, e in kernels):
                skipped += 1
    return 100.0 * skipped / runs if runs else None
