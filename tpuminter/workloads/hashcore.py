"""HashCore-style second workload: seeded function search over a
non-crypto objective (PAPERS.md, arXiv:1902.00112 / 2208.12628).

HashCore's thesis is that the proof-of-work fabric generalizes to
*useful* general-purpose search; PNPCoin runs arbitrary distributed
computation on the same coordinator/worker shape. This module is the
concrete second workload ISSUE 15 ships to prove tpuminter's seam is
real: brute-force search over ``objective(seed, index)`` — a splitmix64
mix, chosen because it is (a) deterministic and stateless per index, so
any chunk partition folds exactly; (b) uniformly distributed, so
threshold variants have tunable hit rates; (c) trivially wide — the
same arithmetic vectorizes on numpy/jnp lanes, which is the engine
seam the cpu/jax workers resolve per-Setup.

Four variants map one-to-one onto the registered fold disciplines:

- ``fmin``   — global minimum over the range (mining's shape, no crypto)
- ``topk``   — the k smallest values, ties at the lowest index
- ``fmatch`` — first index with ``objective <= threshold`` (early-cancel)
- ``fsum``   — map-reduce: total + count over the range

Params ride ``Request.data`` as a tagged + CRC-trailed frame (0xC0) —
the same framing discipline as every other record in the process, so
the codec-conformance checker proves tag/length/CRC invariants over
this codec statically.

Verification semantics (the trust model, per variant): ``fmin``/``topk``
verify the *witnesses* — each claimed (value, index) recomputes, lies
in the chunk range, and the claimed cardinality/order is right — the
same model as mining, where the coordinator rechecks the claimed nonce,
not that no better nonce exists. ``fmatch`` and ``fsum`` claims are
decidable, so they get full recompute proofs: a no-match claim rescans
the chunk (a byzantine "nothing here" would otherwise suppress a real
match) and a sum recomputes exactly. Both run in the coordinator's
verification executor (the scrypt seam), never on the serve loop.
"""

from __future__ import annotations

import os
import struct
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from tpuminter.workloads import Workload, register
from tpuminter.workloads import folds

__all__ = [
    "HashCore", "HashParams", "objective", "pack_params", "VARIANTS",
    "HASHCORE_WID", "set_dev_lanes", "dev_lanes_config",
]

#: Compact workload id on binary WorkResult frames. One process-wide
#: namespace (the analysis suite flags cross-module collisions, like
#: codec tags).
HASHCORE_WID = 1

_U64 = 1 << 64
_M64 = _U64 - 1

#: Params codec: tag ‖ variant:u8 ‖ seed:u64 ‖ threshold:u64 ‖ k:u8 ‖ crc
_TAG_HCPARAMS = 0xC0
_BIN_HCPARAMS = struct.Struct("<BBQQB")
_CRC = struct.Struct("<I")

VARIANTS = ("fmin", "topk", "fmatch", "fsum")

#: Cooperative batch width: the generator yields None between batches
#: so the worker's executor loop stays cancellable, mirroring the
#: mining generators' step discipline.
_BATCH = 2048


def objective(seed: int, index: int) -> int:
    """splitmix64 of ``seed + (index + 1) * golden`` — one u64 per
    global index, stateless, uniform."""
    z = (seed + (index + 1) * 0x9E3779B97F4A7C15) & _M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return z ^ (z >> 31)


def _seal(body: bytes) -> bytes:
    return body + _CRC.pack(zlib.crc32(body))


def pack_params(
    variant: str, seed: int, threshold: int = 0, k: int = 1
) -> bytes:
    """Encode job params for ``Request.data``."""
    if variant not in VARIANTS:
        raise ValueError(f"hashcore: unknown variant {variant!r}")
    if not (0 <= seed < _U64 and 0 <= threshold < _U64):
        raise ValueError("hashcore: seed/threshold out of u64 range")
    if not 1 <= k <= folds.TOPK_SLOTS:
        raise ValueError(f"hashcore: k must be in [1, {folds.TOPK_SLOTS}]")
    return _seal(_BIN_HCPARAMS.pack(
        _TAG_HCPARAMS, VARIANTS.index(variant), seed, threshold, k
    ))


@dataclass(frozen=True)
class HashParams:
    variant: str
    seed: int
    threshold: int
    k: int


def parse_params(data: bytes) -> HashParams:
    """Decode + validate a params frame. Raises ValueError on anything
    malformed — the coordinator Refuses the Request."""
    if len(data) != _BIN_HCPARAMS.size + _CRC.size:
        raise ValueError(
            f"hashcore params: want {_BIN_HCPARAMS.size + _CRC.size} "
            f"bytes, got {len(data)}"
        )
    body, (crc,) = data[:-_CRC.size], _CRC.unpack(data[-_CRC.size:])
    if zlib.crc32(body) != crc:
        raise ValueError("hashcore params: CRC mismatch")
    tag, variant, seed, threshold, k = _BIN_HCPARAMS.unpack(body)
    if tag != _TAG_HCPARAMS:
        raise ValueError(f"hashcore params: tag 0x{tag:02X}")
    if variant >= len(VARIANTS):
        raise ValueError(f"hashcore params: unknown variant {variant}")
    if not 1 <= k <= folds.TOPK_SLOTS:
        raise ValueError("hashcore params: k out of range")
    return HashParams(VARIANTS[variant], seed, threshold, k)


# ---------------------------------------------------------------------------
# engine seam: batch evaluation, resolved per-Setup by the worker
# ---------------------------------------------------------------------------

#: Device-lane knob (ISSUE 17). ``mode``: "auto" routes jax-family
#: backends (jax/tpu/pod) through the u32-pair device engine and keeps
#: cpu workers on host lanes; "on"/"off" force it either way — "off" IS
#: the bit-for-bit A/B baseline (the numpy path below is untouched).
#: ``width``/``rows``/``engine`` pass through to
#: ``ops.splitmix.lane_sweep`` (width None = the autotune probe).
_dev_cfg: Dict[str, Any] = {
    "mode": os.environ.get("TPUMINTER_HC_DEV_LANES", "auto"),
    "width": None,
    "rows": None,
    "engine": "auto",
}

_UNSET = object()


def set_dev_lanes(
    mode: Optional[str] = None,
    *,
    width: Any = _UNSET,
    rows: Any = _UNSET,
    engine: Optional[str] = None,
) -> Dict[str, Any]:
    """Configure the device-lane engine; returns the PRIOR config so
    drills can snapshot/restore. Unspecified fields keep their value."""
    prior = dict(_dev_cfg)
    if mode is not None:
        if mode not in ("auto", "on", "off"):
            raise ValueError(f"dev_lanes mode {mode!r}")
        _dev_cfg["mode"] = mode
    if width is not _UNSET:
        _dev_cfg["width"] = width
    if rows is not _UNSET:
        _dev_cfg["rows"] = rows
    if engine is not None:
        _dev_cfg["engine"] = engine
    return prior


def dev_lanes_config() -> Dict[str, Any]:
    return dict(_dev_cfg)


def _use_dev_lanes(engine: str) -> bool:
    mode = _dev_cfg["mode"]
    if mode == "off":
        return False
    if mode == "on":
        return True
    return engine in ("jax", "tpu", "pod")


def _dev_sweep(p: "HashParams", total: int):
    """Resolve the process-cached LaneSweep for this job's constants.
    Setup errors (no jax on this host, a bad pinned width, a kernel the
    compiler refuses) propagate: device lanes were asked for, so a
    quiet switch to host lanes would hide the device.

    An AUTOTUNED width is clamped so one window does not dwarf the
    chunk: the probe optimizes lanes/s at saturation, but a chunk
    smaller than ``rows × width`` still pays for every masked lane
    (bench_workload_dev's 4096-index arm measured 16× waste before the
    clamp). Chunk sizes are uniform per deployment, so the clamp costs
    one compile, not one per job. A PINNED width is honored verbatim —
    tests pin shapes for deterministic compile reuse."""
    from tpuminter.ops import splitmix

    rows = _dev_cfg["rows"] or splitmix.ROWS
    width = _dev_cfg["width"]
    if width is None:
        width = splitmix.autotune_lane_width(_dev_cfg["engine"], rows=rows)
        per_row = -(-total // rows)
        need = max(128, -(-per_row // 128) * 128)
        width = min(width, need)
    return splitmix.lane_sweep(
        p.variant, k=p.k, engine=_dev_cfg["engine"], width=width, rows=rows,
    )


def _values_vectorized(seed: int, lo: int, hi: int) -> List[int]:
    """One batch on u64 lanes. numpy's wrapping uint64 arithmetic IS
    mod-2^64, so this is bit-exact with :func:`objective`; the u32-pair
    device-lane port of the same expression is ``tpuminter.ops.splitmix``
    (hi/lo word arithmetic, so it needs no x64 flag — the control-plane
    drills run JAX_PLATFORMS=cpu without it, which kept THIS host-lane
    path as the shipped engine until ISSUE 17)."""
    import numpy as np

    idx = np.arange(lo, hi + 1, dtype=np.uint64)
    with np.errstate(over="ignore"):
        z = np.uint64(seed) + (idx + np.uint64(1)) * np.uint64(
            0x9E3779B97F4A7C15
        )
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return z.tolist()


def _values(seed: int, lo: int, hi: int, engine: str) -> List[int]:
    if engine != "cpu":
        try:
            return _values_vectorized(seed, lo, hi)
        except Exception:  # no numpy / exotic dtype host: fall back
            pass
    return [objective(seed, index) for index in range(lo, hi + 1)]


class HashCore(Workload):
    name = "hashcore"
    wid = HASHCORE_WID

    def fold_for(self, request) -> folds.Fold:
        p = parse_params(request.data)
        if p.variant == "fmin":
            return folds.FMin()
        if p.variant == "topk":
            return folds.TopK(p.k)
        if p.variant == "fmatch":
            return folds.FirstMatch(p.threshold)
        return folds.FSum()

    def compute(self, request, fold: folds.Fold, engine: str = "cpu"):
        """Generic batch scan: every variant is ``of_batch`` +
        ``combine``, and first-match stops as soon as ``is_final``
        fires — the worker-side mirror of the coordinator's
        early-cancel. When the device-lane knob routes this backend
        (``set_dev_lanes``), the scan runs as pipelined u32-pair sweep
        windows instead (:meth:`_compute_dev`) — same accumulator,
        same ``searched``, bit for bit."""
        p = parse_params(request.data)
        lo, hi = request.lower, request.upper
        if _use_dev_lanes(engine):
            sweep = _dev_sweep(p, hi - lo + 1)
            return (yield from self._compute_dev(p, fold, lo, hi, sweep))
        acc, searched = fold.initial(), 0
        index = lo
        while index <= hi:
            last = min(hi, index + _BATCH - 1)
            values = _values(p.seed, index, last, engine)
            acc = fold.combine(acc, fold.of_batch(index, values))
            searched += last - index + 1
            if fold.is_final(acc):
                break
            index = last + 1
            yield None
        return searched, acc

    def _compute_dev(self, p, fold: folds.Fold, lo: int, hi: int, sweep):
        """Device-lane scan: dispatch windows of ``rows × width``
        indices depth-2 through ``search.pipeline_spans`` (the dispatch
        latency of window *n+1* overlaps the fold of window *n*),
        resolve ONE packed array per window, and combine the decoded
        chunk-partials — associative folds with deterministic
        tie-breaks, so window granularity produces the same accumulator
        as the host path's ``_BATCH`` granularity.

        The one granularity-dependent output is first-match's early-stop
        ``searched``: the host loop counts whole ``_BATCH`` batches
        through the matching one, so the device path reproduces that
        count *from the match index* rather than from its own window
        size. Early return abandons in-flight handles un-resolved —
        the documented ``pipeline_spans`` contract."""
        from tpuminter.search import pipeline_spans
        from tpuminter.spans import RESOLVE, span

        spans = (
            (g, min(g + sweep.window - 1, hi))
            for g in range(lo, hi + 1, sweep.window)
        )
        acc, searched = fold.initial(), 0
        for (g, e), handle in pipeline_spans(
            spans, lambda s: sweep.dispatch(p.seed, s[0], s[1], p.threshold)
        ):
            with span(RESOLVE):
                part = sweep.resolve(handle, g, e)
            acc = fold.combine(acc, part)
            if fold.is_final(acc):
                match = acc[0]
                searched = min(
                    ((match - lo) // _BATCH + 1) * _BATCH, hi - lo + 1
                )
                return searched, acc
            searched += e - g + 1
            yield None
        return searched, acc

    def verify(self, request, fold: folds.Fold, acc) -> bool:
        p = parse_params(request.data)
        lo, hi = request.lower, request.upper
        if lo > hi:
            return False
        if isinstance(fold, folds.FMin):
            if acc is None:
                return False
            value, index = acc
            return lo <= index <= hi and objective(p.seed, index) == value
        if isinstance(fold, folds.TopK):
            want = min(p.k, hi - lo + 1)
            if len(acc) != want or sorted(map(tuple, acc)) != list(
                map(tuple, acc)
            ):
                return False
            if len({index for _v, index in acc}) != len(acc):
                return False
            return all(
                lo <= index <= hi and objective(p.seed, index) == value
                for value, index in acc
            )
        if isinstance(fold, folds.FirstMatch):
            if acc is None:
                return False  # a dispatched chunk always scans something
            index, value, probes = acc
            if index is None:
                # absence is decidable: a dry claim must cover the whole
                # chunk, and the rescan means a byzantine "no match
                # here" cannot suppress a real one
                return probes == hi - lo + 1 and all(
                    objective(p.seed, j) > p.threshold
                    for j in range(lo, hi + 1)
                )
            if not (lo <= index <= hi and value <= p.threshold
                    and objective(p.seed, index) == value
                    and probes == index - lo + 1):
                return False
            # "first" is part of the claim: the prefix must be dry
            return all(
                objective(p.seed, j) > p.threshold
                for j in range(lo, index)
            )
        if isinstance(fold, folds.FSum):
            total, count = acc
            if count != hi - lo + 1:
                return False
            return total == sum(_values(p.seed, lo, hi, "jax"))
        return False


register(HashCore())
