"""CPU smoke tests for the TPU-gated driver logic (r3 review: a
NameError in ``_mine_rolled_fast``'s search wiring hid behind the TPU
gate because the Pallas kernels only compile on a real chip).

The KERNELS stay TPU-only (tests/test_kernels_tpu.py pins them on
hardware); here they are monkeypatched with CPU fakes so the DRIVERS —
segment iteration, CandidateSearch wiring, pack/resolve handles,
result assembly — execute on every CI run.
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
    """A kernel fake reporting 'no candidate anywhere' (found=0)."""
    return jnp.uint32(0), jnp.uint32(0x7FFFFFFF)


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

    def planted_kernel(template, base, n, tiles, cap):
        b = int(base)
        if b <= win < b + int(n):
            return jnp.uint32(1), jnp.uint32(win - b)
        return jnp.uint32(0), jnp.uint32(0x7FFFFFFF)

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
