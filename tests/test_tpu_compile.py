"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with libtpu compiles for a
chip that is described, not attached, and refuses what the chip's
compiler would refuse (block shapes Mosaic cannot tile, too much fast
memory). Interpret mode on the CPU cannot show either, so these compiles
guard every PR at no chip time. Results and times come only from a run
on the chip (``chip_smoke.py``).

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and an import-time call would give
the xdist workers different tests to collect.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import tpuminter.kernels.sha256 as ksha
import tpuminter.kernels.splitmix as ksplit
from tpuminter import chain
from tpuminter.ops import sha256 as ops

#: the production slab (TpuMiner.DEFAULT_SLAB, and the rolled row width
#: it implies at roll_batch=8)
SLAB = 1 << 27


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.fixture
def compiled_for(one_chip, monkeypatch):
    """``compiled_for(fn, *shapes)`` → the TPU executable's HLO text."""
    monkeypatch.setattr(ksha, "_interpret", lambda: False)
    monkeypatch.setattr(ksplit, "_interpret", lambda: False)

    def compile_(fn, *shapes):
        args = [
            jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes
        ]
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_


U32 = jnp.uint32


def test_search_candidates_compiles_at_production_slab(compiled_for):
    tmpl = ops.header_template(chain.GENESIS_HEADER.pack())
    text = compiled_for(
        lambda base, cap: ksha.pallas_search_candidates(
            tmpl, base, SLAB, 8, cap
        ),
        ((), U32), ((), U32),
    )
    assert "tpu_custom_call" in text


def test_chained_search_candidates_compiles_at_production_slab(compiled_for):
    """TpuMiner's TARGET sweep: chained on the sweep before it
    (``stop`` a packed handle)."""
    tmpl = ops.header_template(chain.GENESIS_HEADER.pack())
    text = compiled_for(
        lambda base, cap, stop: ksha.pallas_search_candidates(
            tmpl, base, SLAB, 8, cap, stop
        ),
        ((), U32), ((), U32), ((3,), U32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [8, 10])
def test_rolled_batch_kernel_compiles(compiled_for, rows):
    """The rolled (extranonce) kernel at TpuMiner's roll_batch=8, in
    both row counts a window dispatches (8 when aligned, 10 padded at
    job edges — ``rolled.lean_plan``): its per-row SMEM inputs were once
    blocked as (1, 8) rows, which Mosaic refuses."""
    text = compiled_for(
        lambda mids, tails, bases, valids, cap:
            ksha.pallas_search_candidates_hdr_batch(
                mids, tails, bases, valids, SLAB, 8, cap
            ),
        ((rows, 8), U32), ((rows, 3), U32), ((rows,), U32),
        ((rows,), jnp.int32), ((), U32),
    )
    assert "tpu_custom_call" in text


def test_splitmix_kernel_compiles(compiled_for):
    n = 1 << 20
    text = compiled_for(
        ksplit.pallas_splitmix_batch,
        ((), U32), ((), U32), ((n,), U32), ((n,), U32),
    )
    assert "tpu_custom_call" in text


def test_interpret_mode_is_restored():
    """The fixtures above patch the kernels for the chip's compiler
    only; this process still runs them in interpret mode on the CPU."""
    assert jax.default_backend() == "cpu"
    assert ksha._interpret() and ksplit._interpret()
    assert os.environ.get("JAX_PLATFORMS", "cpu") == "cpu"
