"""Share of the traced window in which the device ran no op while the miner
child held no work, in %: the chip waiting on the control plane.

Read from the device's ops and the miner child's program spans
(``tpuminter.*`` profiler annotations, on the device trace's clock): the
time inside ``tpuminter.await_chunk`` that no op covers, averaged over the
chips, over the time from the first program span's start to the last
one's end, so that the window's edges, where what the child did is not
recorded, are not charged. The rest of ``device_idle_pct`` is idle while
the child held a chunk: the host loop's, or a drained pipeline's.
"""

from traces import covered, union

AWAIT = "tpuminter.await_chunk"


def _overlap(a, b):
    """Seconds that two sorted lists of disjoint intervals share."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    ours = [ev for ev in run.trace.host if ev[2].startswith("tpuminter.")]
    if not ours:
        return None
    span_s = max(e for _, e, _ in ours) - min(s for s, _, _ in ours)
    waiting = union((s, e) for s, e, name in ours if name == AWAIT)
    idle = sum(
        covered(waiting) - _overlap(waiting, union((s, e) for s, e, _ in run.trace.op_events(d)))
        for d in run.trace.devices
    ) / len(run.trace.devices)
    return 100.0 * idle / span_s if span_s > 0 else None
