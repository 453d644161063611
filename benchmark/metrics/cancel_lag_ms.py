"""Time from a chunk's winner to the miner child's taking the cancel of the
chunk behind it, median over the traced window, in ms.

Read from the miner child's program spans (``tpuminter.*`` profiler
annotations, on the device trace's clock): for each ``tpuminter.winner``
followed by a ``tpuminter.cancel`` before the next winner, the cancel's
start less the winner's end. That is the whole round trip: the child's
Result to the worker, the worker's to the coordinator, the coordinator's
Cancel back through the worker, and the child's wait for its next yield
point, one resolved sweep.
"""

import statistics

WINNER, CANCEL = "tpuminter.winner", "tpuminter.cancel"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lags, won = [], None
    for start, end, name in sorted(ev for ev in run.trace.host if ev[2] in (WINNER, CANCEL)):
        if name == WINNER:
            won = end
        elif won is not None:
            lags.append(start - won)
            won = None
    return 1e3 * statistics.median(lags) if lags else None
