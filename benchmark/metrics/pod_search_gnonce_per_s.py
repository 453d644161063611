"""Nonces per second per chip that the candidate kernel sweeps inside the
pod candidate sweep, in Gnonce/s.

Read from the device trace (``pod_trace.py``): each run of the kernel
inside ``jit_pod_candidate_sweep`` sweeps one slab on its chip, the
configuration's ``miner`` ``slab``, which the benchmark passes to the
worker as ``--slab``. Runs that stopped early at a candidate, shorter
than half the longest on any chip, are left out of both sums, as in
``sha256d_search_gnonce_per_s``.
"""

from pod_trace import pod_runs


def read(run):
    kernels = [d for runs in pod_runs(run.trace).values() for _, ks in runs for d in ks]
    full = [d for d in kernels if d >= 0.5 * max(kernels, default=0.0)]
    if not full:
        return None
    return len(full) * run.config["miner"]["slab"] / sum(full) / 1e9
