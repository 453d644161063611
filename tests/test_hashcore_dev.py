"""Device-lane hashcore engine (ISSUE 17): the u32-pair splitmix64
sweep (``ops.splitmix``) and its Pallas mirror against the two shipped
references — the scalar ``objective`` and the numpy host-lane path.

The A/B contract under test: with the ``dev_lanes`` knob on, every
``HashCore.compute`` output — the accumulator AND ``searched``,
including first-match's early-stop rounding — is bit-for-bit what the
host path produces, at every fold discipline, every ragged tail, and
both sweep engines. All tests run under the tier-1 JAX_PLATFORMS=cpu
config with NO ``jax_enable_x64``: proving the pair arithmetic needs no
u64 dtype is the point.

Shapes are deliberately shared (width 256/512, rows 2) so each
``lru_cache``'d sweep program compiles once per pytest process.
"""

from __future__ import annotations

import random

import pytest

np = pytest.importorskip("numpy")
pytest.importorskip("jax")

from tpuminter.ops import splitmix as sm
from tpuminter.protocol import PowMode, Request
from tpuminter.workloads import folds
from tpuminter.workloads import hashcore as hc

_M64 = (1 << 64) - 1


@pytest.fixture(autouse=True)
def _restore_dev_cfg():
    prior = hc.dev_lanes_config()
    yield
    hc.set_dev_lanes(
        prior["mode"], width=prior["width"], rows=prior["rows"],
        engine=prior["engine"],
    )


def _drive(gen):
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value


def _req(variant, seed, lo, hi, thr=0, k=1):
    return Request(
        job_id=1, mode=PowMode.MIN, lower=lo, upper=hi,
        data=hc.pack_params(variant, seed, thr, k),
        workload="hashcore", chunk_id=0,
    )


# ---------------------------------------------------------------------------
# the pair primitives vs the scalar objective
# ---------------------------------------------------------------------------

def test_lane_objective_matches_scalar_across_domain():
    rng = random.Random(0xD17)
    idx = [rng.getrandbits(rng.choice([8, 32, 63, 64])) for _ in range(64)]
    for seed in (0, 1, rng.getrandbits(64)):
        assert sm.lane_objective(seed, idx) == [
            hc.objective(seed, i) for i in idx
        ]


def test_lane_objective_word_boundaries():
    """The cases u32-pair arithmetic gets wrong when a carry or a
    cross-word shift is off by one: around 2^32 and the u64 wrap."""
    edges = [0, 1, (1 << 32) - 1, 1 << 32, (1 << 32) + 1,
             _M64 - 1, _M64]
    for seed in (0, _M64, 0x9E3779B97F4A7C15):
        assert sm.lane_objective(seed, edges) == [
            hc.objective(seed, i) for i in edges
        ]


def test_pallas_kernel_matches_scalar():
    """The kernel mirror, interpret mode (splitmix is small enough to
    interpret, unlike the SHA bodies — see kernels/splitmix.py)."""
    from tpuminter.kernels.splitmix import pallas_splitmix_batch

    rng = random.Random(5)
    idx = [rng.getrandbits(64) for _ in range(256)]
    ih = np.array([i >> 32 for i in idx], np.uint32)
    il = np.array([i & 0xFFFFFFFF for i in idx], np.uint32)
    vh, vl = pallas_splitmix_batch(np.uint32(7), np.uint32(13), ih, il)
    got = [
        (int(h) << 32) | int(l)
        for h, l in zip(np.asarray(vh), np.asarray(vl))
    ]
    assert got == [hc.objective((7 << 32) | 13, i) for i in idx]


# ---------------------------------------------------------------------------
# sweep programs: every fold ≡ the host of_batch/combine chain
# ---------------------------------------------------------------------------

def _host_acc(fold, seed, lo, hi, batch=2048):
    acc = fold.initial()
    i = lo
    while i <= hi:
        j = min(i + batch - 1, hi)
        vals = [hc.objective(seed, g) for g in range(i, j + 1)]
        acc = fold.combine(acc, fold.of_batch(i, vals))
        if fold.is_final(acc):
            break
        i = j + 1
    return acc


def _dev_acc(fold, variant, seed, lo, hi, engine, thr=0, k=1, width=256):
    sweep = sm.LaneSweep(variant, width, 2, k, engine)
    acc = fold.initial()
    g = lo
    while g <= hi:
        e = min(g + sweep.window - 1, hi)
        acc = fold.combine(
            acc, sweep.resolve(sweep.dispatch(seed, g, e, thr), g, e)
        )
        if fold.is_final(acc):
            break
        g = e + 1
    return acc


def test_sweeps_equal_host_folds_all_variants_ragged():
    """Random (seed, range, threshold, k) at window-misaligned ranges:
    the jnp sweep's window-granular partials combine to the exact host
    accumulator for all four disciplines."""
    rng = random.Random(0xAB)
    for trial in range(8):
        seed = rng.getrandbits(64)
        lo = rng.getrandbits(rng.choice([10, 40, 63]))
        hi = lo + rng.randint(0, 1400)
        k = rng.randint(1, folds.TOPK_SLOTS)
        thr = rng.getrandbits(rng.choice([60, 62, 64]))
        cases = [
            (folds.FMin(), "fmin", 0, 1),
            (folds.TopK(k), "topk", 0, k),
            (folds.FirstMatch(thr), "fmatch", thr, 1),
            (folds.FSum(), "fsum", 0, 1),
        ]
        for fold, variant, t, kk in cases:
            want = _host_acc(fold, seed, lo, hi)
            got = _dev_acc(fold, variant, seed, lo, hi, "jnp", t, kk)
            assert got == want, (variant, seed, lo, hi, t, kk)


def test_pallas_engine_equals_jnp_engine():
    """Same sweep, engine='pallas' (interpret mode): the kernel-backed
    value block feeds the same fold scan to the same bits."""
    rng = random.Random(0xCD)
    for trial in range(2):
        seed = rng.getrandbits(64)
        lo = rng.getrandbits(40)
        hi = lo + rng.randint(0, 900)
        f = folds.FMin()
        assert (
            _dev_acc(f, "fmin", seed, lo, hi, "pallas")
            == _dev_acc(f, "fmin", seed, lo, hi, "jnp")
            == _host_acc(f, seed, lo, hi)
        )


def test_fsum_exact_at_max_values():
    """The 16-bit-limb accumulator carries exactly even when every lane
    is near 2^64 (the column sums' worst case)."""
    f = folds.FSum()
    seed, lo = 0xFFFF_FFFF_FFFF_FFFF, (1 << 63) - 17
    hi = lo + 700
    assert _dev_acc(f, "fsum", seed, lo, hi, "jnp") == _host_acc(
        f, seed, lo, hi
    )


# ---------------------------------------------------------------------------
# the compute seam: knob, searched, setup errors
# ---------------------------------------------------------------------------

def test_compute_seam_device_equals_host_including_searched():
    """End to end through ``HashCore.compute``: (searched, acc) equal
    under the knob for every variant — including first-match's
    early-stop ``searched``, the one granularity-dependent output,
    which the device path must reproduce at host _BATCH rounding."""
    core = hc.HashCore()
    rng = random.Random(0xEF)
    for trial in range(4):
        seed = rng.getrandbits(64)
        lo = rng.getrandbits(rng.choice([8, 40]))
        hi = lo + rng.randint(0, 5000)
        for variant, thr, k in (
            ("fmin", 0, 1),
            ("topk", 0, rng.randint(1, 8)),
            ("fmatch", rng.getrandbits(rng.choice([61, 63])) or 1, 1),
            ("fsum", 0, 1),
        ):
            r = _req(variant, seed, lo, hi, thr, k)
            fold = core.fold_for(r)
            hc.set_dev_lanes("off")
            host = _drive(core.compute(r, fold, engine="jax"))
            hc.set_dev_lanes("on", width=512, rows=2)
            dev = _drive(core.compute(r, fold, engine="cpu"))
            assert dev == host, (variant, seed, lo, hi, thr, k)


def test_fmatch_early_stop_searched_rounding():
    """A guaranteed first-window match: host counts whole _BATCH
    batches through the matching index, device must report the same
    count even though its window size differs."""
    core = hc.HashCore()
    seed = 3
    # find a real match early in the range so both paths early-stop
    lo, hi = 0, 50_000
    vals = [hc.objective(seed, i) for i in range(0, 4096)]
    thr = sorted(vals)[2]
    r = _req("fmatch", seed, lo, hi, thr)
    fold = core.fold_for(r)
    hc.set_dev_lanes("off")
    host = _drive(core.compute(r, fold, engine="jax"))
    hc.set_dev_lanes("on", width=256, rows=2)
    dev = _drive(core.compute(r, fold, engine="cpu"))
    assert dev == host
    searched, acc = dev
    assert acc[0] is not None and searched < hi - lo + 1


def test_knob_off_never_dispatches_on_forces_device():
    core = hc.HashCore()
    r = _req("fmin", 9, 0, 4000)
    fold = core.fold_for(r)
    hc.set_dev_lanes("off")
    before = sm.counters["dispatches"]
    _drive(core.compute(r, fold, engine="jax"))
    assert sm.counters["dispatches"] == before
    hc.set_dev_lanes("on", width=512, rows=2)
    _drive(core.compute(r, fold, engine="cpu"))
    assert sm.counters["dispatches"] > before


def test_knob_auto_routes_jax_family_only():
    hc.set_dev_lanes("auto")
    assert not hc._use_dev_lanes("cpu")
    assert not hc._use_dev_lanes("native")
    for eng in ("jax", "tpu", "pod"):
        assert hc._use_dev_lanes(eng)
    hc.set_dev_lanes("on")
    assert hc._use_dev_lanes("cpu")
    hc.set_dev_lanes("off")
    assert not hc._use_dev_lanes("tpu")


def test_setup_failure_raises_instead_of_host_lanes():
    """A bad pinned width (not a multiple of 128) makes device setup
    fail; with device lanes asked for, that error surfaces — the job
    must not quietly run on host lanes instead."""
    core = hc.HashCore()
    r = _req("fmin", 21, 0, 3000)
    fold = core.fold_for(r)
    hc.set_dev_lanes("on", width=100, rows=2)
    before = sm.counters["dispatches"]
    with pytest.raises(ValueError):
        _drive(core.compute(r, fold, engine="jax"))
    assert sm.counters["dispatches"] == before


# ---------------------------------------------------------------------------
# factories, caching, autotune
# ---------------------------------------------------------------------------

def test_sweep_program_is_cached_per_job_constants():
    """The PR 7 retrace rule: same constants, same compiled program
    object — a fresh jit per job would retrace per chunk."""
    a = sm.sweep_program("fmin", 256, 2, 1, "jnp")
    b = sm.sweep_program("fmin", 256, 2, 1, "jnp")
    c = sm.sweep_program("fmin", 512, 2, 1, "jnp")
    assert a is b and a is not c


def test_sweep_program_rejects_bad_shapes():
    with pytest.raises(ValueError):
        sm.sweep_program("fmin", 100, 2, 1, "jnp")
    with pytest.raises(ValueError):
        sm.sweep_program("fmin", sm.MAX_WIDTH * 2, 2, 1, "jnp")
    with pytest.raises(ValueError):
        sm.sweep_program("nope", 256, 2, 1, "jnp")
    with pytest.raises(ValueError):
        sm.resolve_engine("cuda")


def test_autotune_cache_is_keyed_separately_from_rolled(monkeypatch):
    """The probe caches per (backend, 'hashcore', engine, candidates,
    rows) in its own dict — the only width probe left (the rolled
    sweep's row width is ``rolled.tile_width(nonce_bits, slab)``) — and
    a cache hit returns without probing: no timing, no compile."""
    import tpuminter.search as search

    def no_probe(*_a, **_k):
        raise AssertionError("a cache hit must not probe")

    monkeypatch.setattr(search, "timed_call", no_probe)
    key = ("cpu", "hashcore", "jnp", (256, 512), 2)
    sm._autotune_cache[key] = 512
    try:
        assert sm.autotune_lane_width("jnp", (256, 512), rows=2) == 512
    finally:
        sm._autotune_cache.pop(key, None)


def test_autotune_probes_and_caches_winner():
    key = ("cpu", "hashcore", "jnp", (256, 512), 2)
    sm._autotune_cache.pop(key, None)
    try:
        w = sm.autotune_lane_width("jnp", (256, 512), rows=2, reps=1)
        assert w in (256, 512)
        assert sm._autotune_cache[key] == w
    finally:
        sm._autotune_cache.pop(key, None)


def test_dev_sweep_clamps_autotuned_width_to_chunk():
    """A 4096-index chunk must not pay for an autotuned 16384-lane
    window: the clamp sizes one window to the chunk (bench measured
    16× masked-lane waste without it). Pinned widths are honored."""
    key = ("cpu", "hashcore", "jnp", (2048, 4096, 8192, 16384), 2)
    sm._autotune_cache[key] = 16384
    try:
        hc.set_dev_lanes("on", width=None, rows=2)
        p = hc.parse_params(hc.pack_params("fmin", 1))
        sweep = hc._dev_sweep(p, 4096)
        assert sweep.width == 2048 and sweep.window == 4096
        hc.set_dev_lanes("on", width=512, rows=2)
        assert hc._dev_sweep(p, 4096).width == 512
    finally:
        sm._autotune_cache.pop(key, None)
