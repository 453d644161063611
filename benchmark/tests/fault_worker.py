"""A worker whose miner is broken on purpose, to show that the
benchmark's check sees it. ``TPUMINTER_BENCH_FAULT`` picks the fault:

- ``flip_nonce``: each chunk's answer has one bit of its nonce flipped
  where it is produced;
- ``skip_half``: each chunk mines only the upper half of its range, and
  reports the whole range as searched;
- ``no_search``: each chunk answers at once that it found nothing, its
  state left as it was given.

Usage is that of ``benchmark/worker_main.py``; the fault goes into a
``--backend cpu`` miner in this process and into a device miner in its
child.
"""

from __future__ import annotations

import dataclasses
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import worker_main  # noqa: E402

FAULT_ENV = "TPUMINTER_BENCH_FAULT"


class FaultyMiner:
    def __init__(self, inner, fault: str):
        self._inner = inner
        self._fault = fault
        self.backend = inner.backend
        self.lanes = inner.lanes
        self.span = inner.span
        self.progress_cb = None

    def mine(self, request):
        from tpuminter.protocol import MIN_UNTRACKED, Result

        self._inner.progress_cb = self.progress_cb
        if self._fault == "no_search":
            yield Result(request.job_id, request.mode, request.lower, MIN_UNTRACKED,
                         found=False, searched=request.upper - request.lower + 1,
                         chunk_id=request.chunk_id)
            return
        if self._fault == "skip_half":
            half = (request.upper - request.lower + 1) // 2
            sub = dataclasses.replace(request, lower=request.lower + half)
            for item in self._inner.mine(sub):
                if item is not None and not item.found:
                    item = dataclasses.replace(
                        item, searched=request.upper - request.lower + 1)
                yield item
            return
        for item in self._inner.mine(request):
            if item is not None and self._fault == "flip_nonce":
                item = dataclasses.replace(item, nonce=item.nonce ^ 1)
            yield item

    def compute(self, request):
        return self._inner.compute(request)

    def close(self):
        closer = getattr(self._inner, "close", None)
        if callable(closer):
            closer()


def add_fault(miner):
    fault = os.environ.get(FAULT_ENV)
    return miner if miner is None or not fault else FaultyMiner(miner, fault)


if __name__ == "__mp_main__":
    worker_main.install_child_probes(
        lambda miner: add_fault(worker_main.wrap_miner(miner)))

if __name__ == "__main__":
    from tpuminter import worker

    build = worker._build_miner
    worker._build_miner = lambda *a, **kw: add_fault(build(*a, **kw))
    worker_main.run_worker(sys.argv[1:])
