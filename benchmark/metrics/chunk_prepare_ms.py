"""Time from a chunk's arrival in the miner child to the end of its first
sweep's dispatch, median over the traced window, in ms.

Read from the miner child's program spans (``tpuminter.*`` profiler
annotations, on the device trace's clock): for each
``tpuminter.await_chunk`` followed by a ``tpuminter.dispatch`` before the
next await, that dispatch's end less the await's end. It covers what the
miner does before the device has work: the header template, the target's
words, the kernel's lookup and the first launch.
"""

import statistics

AWAIT, DISPATCH = "tpuminter.await_chunk", "tpuminter.dispatch"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    times, arrived = [], None
    for _, end, name in sorted(ev for ev in run.trace.host if ev[2] in (AWAIT, DISPATCH)):
        if name == AWAIT:
            arrived = end
        elif arrived is not None:
            times.append(end - arrived)
            arrived = None
    return 1e3 * statistics.median(times) if times else None
