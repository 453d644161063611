"""CPU smoke tests for the TPU-gated driver logic (r3 review: a
NameError in ``_mine_rolled_fast``'s search wiring hid behind the TPU
gate because the Pallas kernels only compile on a real chip).

The kernels' speed and full-size results are pinned on hardware
(tests/test_kernels_tpu.py); here they are monkeypatched with CPU fakes
so the DRIVERS — segment iteration, CandidateSearch wiring, pack/resolve
handles, result assembly — execute on every CI run. The chained
candidate sweep's skip runs the kernel itself, in interpret mode at the
smallest size, and the pod program it must leave alone is lowered for a
described chip.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tpuminter import chain, tpu_worker
from tpuminter.protocol import MIN_UNTRACKED, PowMode, Request


def _bare_tpu_miner(slab=1 << 12, roll_batch=8):
    """TpuMiner without __init__ (which refuses the CPU backend)."""
    miner = tpu_worker.TpuMiner.__new__(tpu_worker.TpuMiner)
    miner.slab = slab
    miner.depth = 2
    miner.exact_min = False
    miner.roll_batch = roll_batch
    miner._scrypt_delegate = None
    miner.lanes = 1
    return miner


def _drain(gen):
    result = None
    for item in gen:
        if item is not None:
            result = item
    return result


def _clean_kernel(*_args, **_kw):
    """A chained-kernel fake reporting 'no candidate anywhere' (found=0,
    skipped=0)."""
    return jnp.uint32(0), jnp.uint32(0x7FFFFFFF), jnp.uint32(0)


def test_target_fast_driver_runs_on_cpu(monkeypatch):
    monkeypatch.setattr(
        tpu_worker, "pallas_search_candidates", _clean_kernel
    )
    miner = _bare_tpu_miner()
    req = Request(
        job_id=1, mode=PowMode.TARGET, lower=0, upper=10_000,
        header=chain.GENESIS_HEADER.pack(),
        target=chain.bits_to_target(0x1D00FFFF),
    )
    result = _drain(miner._mine_target_fast(req))
    assert not result.found
    assert result.hash_value == MIN_UNTRACKED
    assert result.searched == 10_001


@pytest.mark.parametrize("roll_batch", [8, 1])
def test_rolled_fast_driver_runs_on_cpu(monkeypatch, roll_batch):
    """The production >2^32 driver: window planning × batched roll ×
    resolve, at the default roll_batch and at a window of one row. This
    exact test catches the r3 resolve NameError class — now with the
    Pallas engine faked at its tpuminter.rolled seam."""
    from tpuminter import rolled

    monkeypatch.setattr(
        rolled, "_pallas_batched_candidate_sweep",
        lambda *a, **k: jnp.asarray(
            np.array([0, 0xFFFFFFFF], np.uint32)
        ),
    )
    rng = np.random.RandomState(1)
    nb, ens = 11, 3
    req = Request(
        job_id=2, mode=PowMode.TARGET, lower=5, upper=(ens << nb) - 9,
        header=chain.GENESIS_HEADER.pack(),
        target=chain.bits_to_target(0x1D00FFFF),
        coinbase_prefix=rng.bytes(41), coinbase_suffix=rng.bytes(60),
        extranonce_size=4, branch=(rng.bytes(32),), nonce_bits=nb,
    )
    miner = _bare_tpu_miner(slab=1 << 10, roll_batch=roll_batch)
    result = _drain(miner._mine_rolled_fast(req))
    assert not result.found
    assert result.hash_value == MIN_UNTRACKED
    assert result.searched == req.upper - req.lower + 1


def test_target_fast_driver_finds_scripted_candidate(monkeypatch):
    """A kernel fake that plants one candidate: the driver must verify
    it host-side, accept the win, and report exact coverage."""
    win = 7_777  # a real winner for an easy-but-capped scripted flow
    header = chain.GENESIS_HEADER.pack()
    import struct

    h_win = chain.hash_to_int(
        chain.dsha256(header[:76] + struct.pack("<I", win))
    )

    def planted_kernel(template, base, n, tiles, cap, stop):
        b = int(base)
        if int(stop[0]) or int(stop[2]):
            return jnp.uint32(0), jnp.uint32(0), jnp.uint32(1)
        if b <= win < b + int(n):
            return jnp.uint32(1), jnp.uint32(win - b), jnp.uint32(0)
        return jnp.uint32(0), jnp.uint32(0x7FFFFFFF), jnp.uint32(0)

    monkeypatch.setattr(
        tpu_worker, "pallas_search_candidates", planted_kernel
    )
    miner = _bare_tpu_miner(slab=1 << 11)
    req = Request(
        job_id=3, mode=PowMode.TARGET, lower=0, upper=20_000,
        header=header, target=h_win,  # the planted candidate wins exactly
    )
    result = _drain(miner._mine_target_fast(req))
    assert result.found
    assert (result.nonce, result.hash_value) == (win, h_win)
    assert result.searched == win + 1


# -- the chained candidate sweep, the kernel itself in interpret mode ---------

#: one loop step of one tile: the smallest sweep the kernel takes
N = 4096
#: 100 nonces below the genesis nonce, whose hash has a zero top word
GEN_BASE = chain.GENESIS_HEADER.nonce - 100


def _cpu_compiled(fn, *args):
    """``fn`` compiled for the CPU with XLA's fusion pass off: the
    interpret-mode SHA kernel then compiles in seconds (minutes with it
    on, ``rolled._jnp_batched_sweep_unfused``)."""
    import jax

    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_disable_hlo_passes": "fusion"}
    )


@pytest.fixture(scope="module")
def cand_sweeps():
    """(unchained, chained) interpret-mode candidate sweeps of N nonces
    of the genesis header."""
    from tpuminter.kernels import pallas_search_candidates
    from tpuminter.ops import sha256 as ops

    tmpl = ops.header_template(chain.GENESIS_HEADER.pack())
    base, stop = jnp.uint32(0), jnp.zeros(3, jnp.uint32)
    plain = _cpu_compiled(
        lambda b: pallas_search_candidates(tmpl, b, N, 1), base
    )
    chained = _cpu_compiled(
        lambda b, s: pallas_search_candidates(tmpl, b, N, 1, None, s),
        base, stop,
    )
    return plain, chained


@pytest.mark.parametrize("base", [GEN_BASE, chain.GENESIS_HEADER.nonce + 1])
def test_chained_sweep_behind_go_equals_the_unchained_sweep(cand_sweeps, base):
    plain, chained = cand_sweeps
    found, off = (int(x) for x in plain(jnp.uint32(base)))
    if base == GEN_BASE:
        assert (found, off) == (1, 100)
    go = tpu_worker._go_handle()
    assert [int(x) for x in chained(jnp.uint32(base), go)] == [found, off, 0]


@pytest.mark.parametrize("stop", [(1, 100, 0), (0, 0, 1), (1, 0, 1)])
def test_chained_sweep_skips_behind_found_or_skipped(cand_sweeps, stop):
    _, chained = cand_sweeps
    out = chained(jnp.uint32(GEN_BASE), jnp.asarray(stop, jnp.uint32))
    assert [int(x) for x in out] == [0, 0, 1]


#: sha256 of the StableHLO of ``jit_pod_candidate_sweep`` (genesis header,
#: slab 2^27 a chip, 4 stripes, a described v5e 2x2), lowered with no
#: source tracebacks in its locations, from the tree before
#: ``pallas_search_candidates`` took ``stop``: the pod program must stay
#: that one
POD_SWEEP_DIGEST = (
    "44a13e22689641c0838095571936d97e9fba7bde8996ec2e50bb8d03cf5be32b"
)


def test_pod_sweep_program_is_unchanged_by_the_chain(v5e_2x2, monkeypatch):
    import hashlib

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    import tpuminter.kernels.sha256 as ksha
    from tpuminter.ops import sha256 as ops
    from tpuminter.parallel.mesh import build_candidate_sweep, make_mesh

    monkeypatch.setattr(ksha, "_interpret", lambda: False)
    mesh = make_mesh(v5e_2x2.devices)
    sweep = build_candidate_sweep(
        mesh, ops.header_template(chain.GENESIS_HEADER.pack()),
        slab_per_device=1 << 27, n_slabs=4, kernel="pallas",
    )
    rep = NamedSharding(mesh, PartitionSpec())
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = sweep.lower(
            jax.ShapeDtypeStruct((), jnp.uint32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        ).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert "module @jit_pod_candidate_sweep " in text
    assert hashlib.sha256(text.encode()).hexdigest() == POD_SWEEP_DIGEST
