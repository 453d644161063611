"""Seconds in which the miner traced programs to jaxprs before the
window: the union of the compile log's tracing spans."""

from traces import covered


def read(run):
    spans = [(e["start"], e["end"]) for e in run.compiles
             if e["kind"] == "trace" and e["end"] <= run.window_wall]
    return covered(spans) if spans else None
