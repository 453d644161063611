"""The miner's host spans (``tpuminter.spans``) land in the profiler's
trace in the order the host loop took them, and cost the worker's own
process no JAX import.

The trace is read as the profiler writes it: the ``*.trace.json.gz``
beside its xplane, Chrome trace events with the span's name and its
args.
"""

import glob
import gzip
import json
import multiprocessing
import os
import subprocess
import sys
import textwrap

import jax

from tpuminter import chain, spans
from tpuminter.jax_worker import JaxMiner
from tpuminter.miner_proc import _run_job
from tpuminter.protocol import PowMode, Request
from tpuminter.search import CandidateSearch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TARGET = chain.bits_to_target(chain.GENESIS_HEADER.bits)
GENESIS_NONCE = chain.GENESIS_HEADER.nonce


def _program_spans(trace_dir):
    """The ``tpuminter.*`` spans of the trace in ``trace_dir``, in start
    order: ``[(name, args)]``."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.trace.json.gz"))
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    ours = [e for e in events if e.get("ph") == "X" and e.get("name", "").startswith("tpuminter.")]
    return [(e["name"], e.get("args", {})) for e in sorted(ours, key=lambda e: e["ts"])]


def _genesis_job(lower, upper, job_id=7, chunk_id=3):
    return Request(
        job_id=job_id, mode=PowMode.TARGET, lower=lower, upper=upper,
        header=chain.GENESIS_HEADER.pack(), target=TARGET, chunk_id=chunk_id,
    )


def _traced_job(tmp_path, request, *, cancel=False):
    """Run ``request`` through the miner child's job loop over a real
    pipe, under the profiler; returns the spans and the messages the
    parent's end received. ``cancel`` puts a cancel on the pipe first:
    the loop takes it at its first yield point, after one sweep."""
    miner = JaxMiner(batch=512)
    for _ in miner.mine(_genesis_job(0, 511)):  # compile outside the trace
        pass
    parent, child = multiprocessing.Pipe()
    try:
        if cancel:
            parent.send(("cancel",))
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert _run_job(child, miner, "mine", request, False)
        finally:
            jax.profiler.stop_trace()
        got = []
        while parent.poll():
            got.append(parent.recv())
    finally:
        parent.close()
        child.close()
    return _program_spans(str(tmp_path)), got


def test_target_job_records_fifo_sweeps_and_one_winner(tmp_path):
    # three 512-nonce batches; the genesis nonce is in the third
    request = _genesis_job(GENESIS_NONCE - 1200, GENESIS_NONCE + 300)
    recorded, got = _traced_job(tmp_path, request)
    assert [m[0] for m in got] == ["step", "step", "result"]
    assert got[-1][1].found and got[-1][1].nonce == GENESIS_NONCE
    names = [name for name, _ in recorded]
    assert names == [spans.DISPATCH, spans.RESOLVE] * 3 + [spans.WINNER]
    assert recorded[-1][1] == {"job": "7", "chunk": "3"}


def test_cancel_mid_job_records_a_cancel_and_no_winner(tmp_path):
    request = _genesis_job(GENESIS_NONCE + 1, GENESIS_NONCE + 512 * 64)
    recorded, got = _traced_job(tmp_path, request, cancel=True)
    assert got == [("end",)]
    names = [name for name, _ in recorded]
    assert names == [spans.DISPATCH, spans.RESOLVE, spans.CANCEL]
    assert recorded[-1][1] == {"job": "7", "chunk": "3"}


def test_candidate_search_leaves_one_sweep_unresolved_behind_its_winner(tmp_path):
    """Depth 2: the winner's slab resolves while the slab behind it is
    in flight, and that one is never resolved."""
    slab = 1 << 10

    def sweep(base, n, after):
        return (1, 5, 0) if base == 0 else (0, 0, 0)

    def verify(nonce):
        return True, nonce

    search = CandidateSearch(sweep, lambda h: h, verify, 0, 8 * slab - 1,
                             slab=slab, depth=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in search.events():
            pass
    finally:
        jax.profiler.stop_trace()
    assert search.outcome.found and search.outcome.nonce == 5
    names = [name for name, _ in _program_spans(str(tmp_path))]
    assert names == [spans.DISPATCH, spans.DISPATCH, spans.RESOLVE]


def test_the_workers_process_stays_off_jax():
    script = textwrap.dedent("""
        import sys
        import tpuminter.worker, tpuminter.miner_proc, tpuminter.search
        from tpuminter import spans
        for name in (spans.AWAIT_CHUNK, spans.WINNER):
            with spans.span(name, job=1, chunk=2):
                pass
        print("jax" in sys.modules)
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, capture_output=True,
        text=True, timeout=120, check=True,
    )
    assert out.stdout.strip() == "False"
