"""Stripes that one run of the pod candidate sweep takes before its ICI
or-reduce stops it, mean over the chips' runs in the traced window.

Read from the device trace: the candidate-kernel op events on a chip's
``XLA Ops`` line inside each ``jit_pod_candidate_sweep`` run on its
``XLA Modules`` line, one a stripe (``pod_trace.py``). 1 where the
or-reduce fires after the first stripe; the pod's ``n_slabs`` (4) for a
run that meets no candidate.
"""

from pod_trace import pod_runs


def read(run):
    counts = [len(kernels) for runs in pod_runs(run.trace).values()
              for _, kernels in runs]
    return sum(counts) / len(counts) if counts else None
