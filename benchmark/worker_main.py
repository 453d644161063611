"""Runs the worker's own command line, ``tpuminter.worker.main``, with
probes in the device-miner child it spawns.

- Each log line of the child carries its wall-clock time, so the
  compile log's spans can be placed against the measured window.
- Where ``TPUMINTER_BENCH_OUT`` names a directory, the child writes
  ``memory.json`` there after its first job (a warm-up job) and again
  when the worker closes its miner on the way out: the device's peak
  bytes in use as JAX reports it, and the footprint of every program
  compiled in the process. At close it also writes ``steps.jsonl``: the
  wall-clock time of each chunk's start and of each device step the
  miner resolved in it, and every JAX trace, lowering or compile and
  every full garbage collection of the process that took 10 ms or
  more, all kept in memory until then, so that no job of the measured
  window pays for a write.
- Where ``TPUMINTER_BENCH_TRACE`` names a directory, a thread of the
  child records one ``jax.profiler`` trace into it: it starts when the
  file ``start`` appears there and stops when ``stop`` does, and then
  writes ``window.json`` with the wall-clock times of both.

The worker spawns its device miner with ``multiprocessing``'s spawn
method, which runs this file again in the child under the name
``__mp_main__`` before it unpickles the miner's factory. That is where
the probes go in: the factory that the child looks up by name is
wrapped, and nothing else of the worker changes.

Usage: ``python benchmark/worker_main.py <host:port> --backend tpu ...``
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time

OUT_ENV = "TPUMINTER_BENCH_OUT"
TRACE_ENV = "TPUMINTER_BENCH_TRACE"
LOG_FORMAT = "%(created).6f %(levelname)s:%(name)s:%(message)s"


def memory_reading() -> dict:
    """Device memory as JAX reports it, on the fullest device.

    ``memory_stats()`` counts the arrays a process holds; a program's
    scratch (its temporaries, such as scrypt's ROMix table) is not among
    them. So each compiled program's footprint (arguments, outputs,
    temporaries and code, as its compiled memory analysis gives them)
    is read too, and the peak is the larger of the two on each device:
    a floor under what the device held while the largest program ran.
    """
    import jax
    from jax.extend import backend

    programs = []
    for exe in backend.get_backend().live_executables():
        m = exe.get_compiled_memory_stats()
        size = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes
                + m.generated_code_size_in_bytes)
        names = [mod.name for mod in exe.hlo_modules()]
        programs.append((size, names[0] if names else "?",
                         {d.id for d in exe.local_devices()}))
    devices = []
    for d in jax.local_devices():
        arrays = (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        largest = max([(size, name) for size, name, ids in programs if d.id in ids],
                      default=(0, None))
        devices.append({"id": d.id, "peak_bytes_in_use": arrays,
                        "largest_program": largest[1], "largest_program_bytes": largest[0],
                        "peak_bytes": max(arrays, largest[0])})
    fullest = max(devices, key=lambda r: r["peak_bytes"])
    top = sorted(((size, name) for size, name, _ in programs), reverse=True)[:5]
    return {"peak_bytes": fullest["peak_bytes"], "fullest": fullest,
            "programs": [[name, size] for size, name in top]}


#: the shortest pause that the event probes record, in seconds
PAUSE_S = 0.01
#: rows of steps.jsonl that are not the miner's own: [start, "jax" or
#: "gc", what, seconds]
EVENTS: list = []


def record_pauses() -> None:
    """Record JAX's compile spans and the collector's full passes that
    last PAUSE_S or more into EVENTS."""
    import gc

    from jax import monitoring

    def on_span(event, start, end, **kw):
        if end - start >= PAUSE_S:
            EVENTS.append([start, "jax", f"{event.rsplit('/', 1)[-1]} {kw.get('fun_name')}",
                           end - start])

    started = {}

    def on_gc(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            started["t"] = time.time()
        elif "t" in started and time.time() - started["t"] >= PAUSE_S:
            EVENTS.append([started["t"], "gc", f"collected {info['collected']}",
                           time.time() - started["t"]])

    monitoring.register_event_time_span_listener(on_span)
    gc.callbacks.append(on_gc)


class MinerProbe:
    """Wraps a device miner; records its memory after the first job and
    when it is closed, and the time of every step it takes."""

    def __init__(self, inner, out_dir: str):
        self._inner = inner
        self._dir = out_dir
        self._written = False
        self._steps = []
        self.backend = inner.backend
        self.lanes = inner.lanes
        self.span = inner.span
        self.progress_cb = None

    def _record(self) -> None:
        _write_json(os.path.join(self._dir, "memory.json"), memory_reading())
        self._written = True

    def _job(self, request, gen):
        self._steps.append([time.time(), "start", request.job_id,
                            request.chunk_id, request.lower, request.upper])
        try:
            for item in gen:
                self._steps.append([time.time(), "step" if item is None else "result"])
                yield item
        finally:
            gen.close()  # a cancelled chunk ends its miner's job at once
            if not self._written:
                self._record()

    def mine(self, request):
        self._inner.progress_cb = self.progress_cb
        return self._job(request, self._inner.mine(request))

    def compute(self, request):
        self._inner.progress_cb = self.progress_cb
        return self._job(request, self._inner.compute(request))

    def close(self):
        self._record()
        rows = sorted(self._steps + EVENTS, key=lambda row: row[0])
        with open(os.path.join(self._dir, "steps.jsonl"), "w") as fh:
            fh.writelines(json.dumps(row) + "\n" for row in rows)
        closer = getattr(self._inner, "close", None)
        if callable(closer):
            closer()


def _write_json(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(obj, fh)
    os.replace(tmp, path)


def wrap_miner(miner):
    """The probes every benchmark worker's device miner carries."""
    out_dir = os.environ.get(OUT_ENV)
    if miner is None or not out_dir:
        return miner
    return MinerProbe(miner, out_dir)


def _trace_on_request(trace_dir: str) -> None:
    """Start and stop one profiler trace when the files ``start`` and
    ``stop`` appear in ``trace_dir``."""
    import jax

    def wait_for(name):
        while not os.path.exists(os.path.join(trace_dir, name)):
            time.sleep(0.02)

    wait_for("start")
    jax.profiler.start_trace(trace_dir)
    t_start = time.time()
    with open(os.path.join(trace_dir, "started"), "w") as fh:
        fh.write(str(t_start))
    wait_for("stop")
    t_stop = time.time()
    jax.profiler.stop_trace()
    _write_json(os.path.join(trace_dir, "window.json"),
                {"start": t_start, "stop": t_stop})


def install_child_probes(wrap=wrap_miner) -> None:
    """In the spawned miner child: timestamped log lines, the trace
    thread where one is asked for, and the factory
    ``tpuminter.miner_proc.device_miner`` wrapped by ``wrap``."""
    logging.basicConfig(level=logging.INFO, format=LOG_FORMAT)
    if os.environ.get(OUT_ENV):
        record_pauses()
    from tpuminter import miner_proc

    factory = miner_proc.device_miner

    def device_miner(*args, **kwargs):
        miner = factory(*args, **kwargs)
        trace_dir = os.environ.get(TRACE_ENV)
        if trace_dir:
            threading.Thread(
                target=_trace_on_request, args=(trace_dir,), daemon=True,
                name="bench-trace",
            ).start()
        return wrap(miner)

    miner_proc.device_miner = device_miner


def run_worker(argv) -> None:
    logging.basicConfig(level=logging.INFO, format=LOG_FORMAT)
    from tpuminter.worker import main

    try:
        main(argv)
    except KeyboardInterrupt:
        pass  # the benchmark's stop signal; main's finally has run


if __name__ == "__mp_main__":
    install_child_probes()

if __name__ == "__main__":
    run_worker(sys.argv[1:])
