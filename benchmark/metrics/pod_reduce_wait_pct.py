"""Share of the pod candidate sweep's device time, summed over the chips,
in which no candidate kernel ran, in %.

Read from the device trace (``pod_trace.py``): each run of
``jit_pod_candidate_sweep`` less its candidate-kernel ops. What is left
is the ICI or-reduce after every stripe and the wait at it: a chip whose
kernel stopped early at a candidate waits there for the chips still
sweeping their slabs.
"""

from pod_trace import pod_runs


def read(run):
    total = kernel = 0.0
    for runs in pod_runs(run.trace).values():
        for seconds, kernels in runs:
            total += seconds
            kernel += sum(kernels)
    return 100.0 * (total - kernel) / total if total > 0 else None
