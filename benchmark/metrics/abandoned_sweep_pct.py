"""Sweeps that no job used, over sweeps dispatched, in the traced window, in %.

Read from the miner child's program spans (``tpuminter.*`` profiler
annotations, on the device trace's clock). A sweep is a
``tpuminter.dispatch``. Chunks are delimited by ``tpuminter.await_chunk``:
one runs from an await's end to the next await's start, so only chunks
whole in the window count. A chunk's sweeps go unused when the chunk ends
in a ``tpuminter.cancel`` (all of them: its job was answered elsewhere),
or, when it sends a ``tpuminter.winner``, those still unresolved then
(results resolve in dispatch order: its dispatches less its
``tpuminter.resolve`` spans). Every sweep of a header job is a full slab,
so the count is a share of the device's work.
"""

AWAIT, DISPATCH, RESOLVE, WINNER, CANCEL = (
    "tpuminter." + s for s in ("await_chunk", "dispatch", "resolve", "winner", "cancel"))


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    events = sorted(ev for ev in run.trace.host
                    if ev[2] in (AWAIT, DISPATCH, RESOLVE, WINNER, CANCEL))
    dispatched = abandoned = 0
    chunk = None  # [dispatches, resolves, unused] of the chunk in progress
    for _, _, name in events:
        if name == AWAIT:
            if chunk is not None:
                dispatched += chunk[0]
                abandoned += chunk[2]
            chunk = [0, 0, 0]
        elif chunk is None:
            continue  # the window opened inside this chunk
        elif name == DISPATCH:
            chunk[0] += 1
        elif name == RESOLVE:
            chunk[1] += 1
        elif name == WINNER:
            chunk[2] = chunk[0] - chunk[1]
        else:
            chunk[2] = chunk[0]
    return 100.0 * abandoned / dispatched if dispatched else None
