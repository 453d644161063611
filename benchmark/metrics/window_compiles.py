"""Programs the miner compiled or loaded from the cache after the
window had started (there should be none)."""


def read(run):
    if not run.compiles:
        return None
    return sum(1 for e in run.compiles
               if e["kind"] == "compile" and e["end"] > run.window_wall)
