"""Nonces that the SHA-256d search programs sweep per second of their own
device time, in Gnonce/s.

A run of a search program sweeps what its dispatch holds: ``slab``
nonces for the header kernel, ``roll_batch`` rows of ``slab`` for the
batched rolled kernel. Both are the configuration's ``miner`` sizes,
which the benchmark passes to the worker as ``--slab`` and
``--roll-batch``; the kernels take them as static sizes, so the compile
log's argument shapes do not show them. Runs that
stopped early at a candidate, shorter than half the longest run of
their program in the trace, are left out of both sums.
"""

PROGRAMS = {
    "jit_pallas_search_candidates": lambda m: m["slab"],
    "jit__pallas_batched_candidate_sweep": lambda m: m["roll_batch"] * m["slab"],
}


def read(run):
    if run.trace is None:
        return None
    nonces = seconds = 0.0
    for device in run.trace.devices:
        for program, per_run in PROGRAMS.items():
            runs = [e - s for s, e, name in run.trace.programs(device) if name == program]
            if runs:
                full = [d for d in runs if d >= 0.5 * max(runs)]
                nonces += len(full) * per_run(run.config["miner"])
                seconds += sum(full)
    return nonces / seconds / 1e9 if seconds else None
