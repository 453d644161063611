"""Seconds in which the miner lowered programs and compiled them or
loaded them from the persistent cache, before the window: the union of
the compile log's lowering and compile spans."""

from traces import covered


def read(run):
    spans = [(e["start"], e["end"]) for e in run.compiles
             if e["kind"] in ("lower", "compile") and e["end"] <= run.window_wall]
    return covered(spans) if spans else None
