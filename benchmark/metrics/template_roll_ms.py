"""Host time of the first extranonce roll of each chunk, mean over the
chunks in the traced window, in ms: what switching to a new template
costs the miner child before its first sweep can go.

Read from the miner child's program spans (``tpuminter.*`` profiler
annotations, on the device trace's clock): for each
``tpuminter.await_chunk``, the first ``tpuminter.roll`` span before the
next await. That span builds the template's words on the host, sends
them to the device and dispatches the roll; where the roll's program is
traced and compiled for each template, that is in it too. None where the
trace has no roll span (a miner without one).
"""

AWAIT, ROLL = "tpuminter.await_chunk", "tpuminter.roll"


def read(run):
    if run.trace is None:
        return None
    firsts, arrived = [], False
    for start, end, name in sorted(ev for ev in run.trace.host if ev[2] in (AWAIT, ROLL)):
        if name == AWAIT:
            arrived = True
        elif arrived:
            firsts.append(end - start)
            arrived = False
    return 1e3 * sum(firsts) / len(firsts) if firsts else None
