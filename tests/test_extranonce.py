"""ExtraNonce / Merkle-roll tests (BASELINE.json:9-10; SURVEY.md §7
stage 6): the device roll is pinned bit-for-bit to the host reference
(``chain.rolled_header`` → ``hashlib``), the rolled miners are pinned to
brute force, and a rolled job runs end-to-end through the cluster with
the winning extranonce ≥ 1 — i.e. a search that actually exhausted a
(shrunken, ``nonce_bits``-wide) nonce space and rolled past it.

The fixture is deterministic: seed 0's global argmin lands at
extranonce 2 (asserted, not assumed). ``nonce_bits=10`` shrinks the
per-extranonce space so the roll happens within a CI-sized sweep; the
full-width (2^32) roll runs on the real chip in tests/test_kernels_tpu.
"""

import asyncio
import struct

import numpy as np
import jax.numpy as jnp
import pytest

from tpuminter import chain
from tpuminter.ops import sha256 as ops
from tpuminter.ops import merkle
from tpuminter.protocol import PowMode, Request, decode_msg, encode_msg
from tpuminter.worker import CpuMiner

NB = 10  # nonce_bits under test
ENS = 4  # extranonce values covered


def fixture(seed: int = 0):
    rng = np.random.RandomState(seed)
    prefix = rng.bytes(41)  # odd sizes: unaligned extranonce hole
    suffix = rng.bytes(60)
    branch = [rng.bytes(32) for _ in range(2)]
    return prefix, suffix, branch, chain.GENESIS_HEADER.pack()


def brute(prefix, suffix, branch, hdr80):
    """(hash, global index) for every index in the fixture space."""
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    out = []
    for en in range(ENS):
        p76 = chain.rolled_header(hdr80, cb, branch, en).pack()[:76]
        for n in range(1 << NB):
            h = chain.hash_to_int(chain.dsha256(p76 + struct.pack("<I", n)))
            out.append((h, (en << NB) | n))
    return out


@pytest.fixture(scope="module")
def ground_truth():
    prefix, suffix, branch, hdr80 = fixture()
    all_h = brute(prefix, suffix, branch, hdr80)
    h_min, g_min = min(all_h)
    assert g_min >> NB == 2, "fixture invariant: winner at extranonce 2"
    return prefix, suffix, branch, hdr80, all_h, h_min, g_min


# ---------------------------------------------------------------------------
# host primitives
# ---------------------------------------------------------------------------

def test_split_global():
    assert chain.split_global(0, 32) == (0, 0)
    assert chain.split_global((5 << 32) | 77, 32) == (5, 77)
    assert chain.split_global((3 << 10) | 1023, 10) == (3, 1023)


def test_rolled_header_matches_manual_merkle():
    prefix, suffix, branch, hdr80 = fixture()
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    for en in (0, 1, 0xDEADBEEF):
        txid = chain.dsha256(prefix + en.to_bytes(4, "little") + suffix)
        root = chain.merkle_root_from_branch(txid, branch)
        hdr = chain.rolled_header(hdr80, cb, branch, en)
        assert hdr.merkle_root == root
        # everything but the root is untouched
        base = chain.BlockHeader.unpack(hdr80)
        assert (hdr.version, hdr.prev_hash, hdr.timestamp, hdr.bits) == (
            base.version, base.prev_hash, base.timestamp, base.bits
        )


# ---------------------------------------------------------------------------
# the device roll (jnp path; Pallas twin tested on the real chip)
# ---------------------------------------------------------------------------

def _roll_one(hdr80, prefix, suffix, en_size, branch):
    """``roll(en) -> (midstate, tail_words)``: row 0 of the batched
    device roll of the single extranonce ``en``."""
    batch = merkle.make_extranonce_roll_batch(
        hdr80, prefix, suffix, en_size, branch)

    def roll(en):
        mids, tails = batch(
            jnp.asarray([en >> 32], jnp.uint32),
            jnp.asarray([en & 0xFFFFFFFF], jnp.uint32),
        )
        return mids[0], tails[0]

    return roll


def test_device_roll_matches_host_template():
    """roll(en) ≡ header_template(rolled_header(en)) for midstate AND
    tail words — the exact values the search kernels specialize on."""
    prefix, suffix, branch, hdr80 = fixture()
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    roll = _roll_one(hdr80, prefix, suffix, 4, branch)
    for en in (0, 1, 2, 0xDEADBEEF):
        want_hdr = chain.rolled_header(hdr80, cb, branch, en)
        t = ops.header_template(want_hdr.pack())
        mid, tw = roll(en)
        assert tuple(int(x) for x in np.asarray(mid)) == t.midstate
        assert tuple(int(x) for x in np.asarray(tw)) == want_hdr.tail_words()


def test_device_roll_wide_extranonce():
    """8-byte extranonces travel as (hi, lo) u32 pairs."""
    prefix, suffix, branch, hdr80 = fixture()
    cb = chain.CoinbaseTemplate(prefix, suffix, 8)
    roll = _roll_one(hdr80, prefix, suffix, 8, branch)
    en = 0x0123456789ABCDEF
    want = ops.header_template(chain.rolled_header(hdr80, cb, branch, en).pack())
    mid, _ = roll(en)
    assert tuple(int(x) for x in np.asarray(mid)) == want.midstate


def test_device_roll_empty_branch():
    """A block whose only tx is the coinbase: root == txid."""
    prefix, suffix, _, hdr80 = fixture()
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    roll = _roll_one(hdr80, prefix, suffix, 4, ())
    want = ops.header_template(chain.rolled_header(hdr80, cb, (), 9).pack())
    mid, tw = roll(9)
    assert tuple(int(x) for x in np.asarray(mid)) == want.midstate


def test_header_digest_dyn_matches_hashlib():
    """The dynamic-header hash fed by the roll ≡ hashlib double-SHA."""
    prefix, suffix, branch, hdr80 = fixture()
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    roll = _roll_one(hdr80, prefix, suffix, 4, branch)
    for en in (0, 3):
        mid, tw = roll(en)
        nonces = jnp.asarray(np.array([0, 1, 77, 2**32 - 1], np.uint32))
        dw = np.asarray(ops.header_digest_dyn(mid, tw, nonces))
        p76 = chain.rolled_header(hdr80, cb, branch, en).pack()[:76]
        for i, n in enumerate([0, 1, 77, 2**32 - 1]):
            want = chain.dsha256(p76 + struct.pack("<I", n))
            got = b"".join(int(w).to_bytes(4, "big") for w in dw[i])
            assert got == want, (en, n)


# ---------------------------------------------------------------------------
# protocol plumbing
# ---------------------------------------------------------------------------

def test_rolled_request_roundtrip():
    prefix, suffix, branch, hdr80 = fixture()
    req = Request(
        job_id=5, mode=PowMode.TARGET, lower=0, upper=(ENS << NB) - 1,
        header=hdr80, target=123456789,
        coinbase_prefix=prefix, coinbase_suffix=suffix,
        extranonce_size=4, branch=tuple(branch), nonce_bits=NB,
    )
    assert req.rolled
    got = decode_msg(encode_msg(req))
    assert got == req


def test_rolled_request_validation():
    prefix, suffix, branch, hdr80 = fixture()
    from tpuminter.protocol import ProtocolError

    with pytest.raises(ProtocolError):  # rolling is TARGET-only
        Request(job_id=1, mode=PowMode.MIN, lower=0, upper=10,
                data=b"x", coinbase_prefix=prefix)
    with pytest.raises(ProtocolError):  # upper beyond the global space
        Request(job_id=1, mode=PowMode.TARGET, lower=0,
                upper=1 << (NB + 32), header=hdr80, target=1,
                coinbase_prefix=prefix, nonce_bits=NB)
    with pytest.raises(ProtocolError):  # bad branch entry
        Request(job_id=1, mode=PowMode.TARGET, lower=0, upper=10,
                header=hdr80, target=1, coinbase_prefix=prefix,
                branch=(b"short",))


# ---------------------------------------------------------------------------
# miners vs brute force
# ---------------------------------------------------------------------------

def _rolled_request(ground_truth, target, lower=0, upper=None, job_id=1):
    prefix, suffix, branch, hdr80, _, _, _ = ground_truth
    return Request(
        job_id=job_id, mode=PowMode.TARGET,
        lower=lower, upper=(ENS << NB) - 1 if upper is None else upper,
        header=hdr80, target=target,
        coinbase_prefix=prefix, coinbase_suffix=suffix,
        extranonce_size=4, branch=tuple(branch), nonce_bits=NB,
    )


def drain(gen):
    result = None
    for item in gen:
        if item is not None:
            result = item
    return result


def test_cpu_miner_rolls_to_winner(ground_truth):
    *_, all_h, h_min, g_min = ground_truth
    req = _rolled_request(ground_truth, target=h_min)
    result = drain(CpuMiner(batch=256).mine(req))
    assert result.found
    assert (result.nonce, result.hash_value) == (g_min, h_min)
    assert result.nonce >> NB >= 1  # the roll actually happened
    # first-winner semantics: nothing below g_min wins
    assert all(h > h_min for h, g in all_h if g < g_min)
    assert result.searched == g_min + 1


def test_cpu_miner_rolled_exhausted_reports_min(ground_truth):
    *_, h_min, g_min = ground_truth
    req = _rolled_request(ground_truth, target=1)  # unbeatable
    result = drain(CpuMiner(batch=256).mine(req))
    assert not result.found
    assert (result.hash_value, result.nonce) == (h_min, g_min)
    assert result.searched == ENS << NB


def test_jax_miner_rolled_matches_cpu(ground_truth):
    from tpuminter.jax_worker import JaxMiner

    *_, h_min, g_min = ground_truth
    req = _rolled_request(ground_truth, target=h_min)
    result = drain(JaxMiner(batch=512).mine(req))
    assert result.found
    assert (result.nonce, result.hash_value) == (g_min, h_min)

    req = _rolled_request(ground_truth, target=1)
    result = drain(JaxMiner(batch=512).mine(req))
    assert not result.found
    assert (result.hash_value, result.nonce) == (h_min, g_min)


def test_jax_miner_rolled_partial_chunk(ground_truth):
    """A chunk that starts mid-segment and ends mid-segment (what the
    coordinator's carving produces) still maps global indices right."""
    from tpuminter.jax_worker import JaxMiner

    prefix, suffix, branch, hdr80, all_h, _, _ = ground_truth
    lo, hi = (1 << NB) + 100, (3 << NB) + 50  # en 1..3, ragged edges
    want = min((h, g) for h, g in all_h if lo <= g <= hi)
    req = _rolled_request(ground_truth, target=1, lower=lo, upper=hi)
    result = drain(JaxMiner(batch=512).mine(req))
    assert not result.found
    assert (result.hash_value, result.nonce) == want


# ---------------------------------------------------------------------------
# end-to-end through the cluster (eval configs 3-4 shape)
# ---------------------------------------------------------------------------

def test_realistic_rolled_job_via_client_cli():
    """A mainnet-scale rolled job — 250-byte coinbase, 12-deep merkle
    branch — encodes to more than one LSP frame (VERDICT r3 missing #1)
    and must still travel the REAL client CLI path (a subprocess running
    ``python -m tpuminter.client``) to a winner a mixed fleet mines and
    the coordinator host-verifies. Exercises LSP fragmentation on the
    submit leg and the Setup/Assign template split on the dispatch leg."""
    import sys

    from tests.test_e2e import run
    from tpuminter.coordinator import Coordinator
    from tpuminter.jax_worker import JaxMiner
    from tpuminter.lsp.message import MAX_PAYLOAD
    from tpuminter.lsp.params import FAST as LSP_FAST
    from tpuminter.worker import run_miner

    rng = np.random.RandomState(7)
    prefix, suffix = rng.bytes(120), rng.bytes(126)
    branch = [rng.bytes(32) for _ in range(12)]
    hdr80 = chain.GENESIS_HEADER.pack()
    assert len(prefix) + 4 + len(suffix) == 250  # the realistic coinbase

    # pick a target a CI-sized sweep of extranonce 0 can beat: the min
    # over its first 40k nonces, rounded UP to a representable compact
    # (truncation rounds down, which would un-win the winner)
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    p76 = chain.rolled_header(hdr80, cb, branch, 0).pack()[:76]
    h_min = min(
        chain.hash_to_int(chain.dsha256(p76 + struct.pack("<I", n)))
        for n in range(40_000)
    )
    bits = chain.target_to_bits(h_min)
    if chain.bits_to_target(bits) < h_min:
        bits += 1
    target = chain.bits_to_target(bits)
    assert target >= h_min

    # the submitted Request genuinely exceeds one LSP frame
    probe = Request(
        job_id=1, mode=PowMode.TARGET, lower=0, upper=(3 << 32) | 0xFFFFFFFF,
        header=hdr80, target=target, coinbase_prefix=prefix,
        coinbase_suffix=suffix, extranonce_size=4, branch=tuple(branch),
    )
    assert len(encode_msg(probe)) > MAX_PAYLOAD

    async def scenario():
        # production (lsp.params.FAST) timing on both sides: the CLI
        # subprocess heartbeats at 250 ms epochs, so the coordinator must
        # tolerate that cadence
        coord = await Coordinator.create(params=LSP_FAST, chunk_size=8192)
        serve = asyncio.ensure_future(coord.serve())
        miners = [
            asyncio.ensure_future(run_miner(
                "127.0.0.1", coord.port, CpuMiner(), params=LSP_FAST)),
            asyncio.ensure_future(run_miner(
                "127.0.0.1", coord.port, JaxMiner(batch=8192, lanes=2),
                params=LSP_FAST)),
        ]
        await asyncio.sleep(0.2)
        argv = [
            sys.executable, "-m", "tpuminter.client",
            f"127.0.0.1:{coord.port}",
            "--header", hdr80.hex(), "--bits", hex(bits),
            "--coinbase-prefix", prefix.hex(),
            "--coinbase-suffix", suffix.hex(),
            "--extranonce-size", "4", "--max-extranonce", "3",
        ]
        for sib in branch:
            argv += ["--branch", sib.hex()]
        try:
            proc = await asyncio.create_subprocess_exec(
                *argv,
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE,
            )
            out, err = await asyncio.wait_for(proc.communicate(), 90.0)
            line = out.decode().strip()
            assert line.startswith("Result "), (line, err.decode())
            _, hash_hex, en_part, n_part = line.split()
            en = int(en_part.split("=")[1])
            n = int(n_part.split("=")[1])
            # independent re-verification of the printed winner
            p76w = chain.rolled_header(hdr80, cb, branch, en).pack()[:76]
            digest = chain.dsha256(p76w + struct.pack("<I", n))
            assert chain.hash_to_hex(digest) == hash_hex
            assert chain.hash_to_int(digest) <= target
            assert coord.stats["results_rejected"] == 0
        finally:
            for t in miners:
                t.cancel()
            serve.cancel()
            await asyncio.gather(*miners, serve, return_exceptions=True)
            await coord.close()

    run(scenario(), timeout=150.0)


def test_rolled_job_end_to_end(ground_truth):
    from tests.test_e2e import FAST, Cluster, run
    from tpuminter.client import submit

    *_, h_min, g_min = ground_truth

    async def scenario():
        cluster = await Cluster.create(
            n_miners=2, chunk_size=300,
            miner_factory=lambda: CpuMiner(batch=128),
        )
        try:
            req = _rolled_request(ground_truth, target=h_min, job_id=42)
            result = await submit(
                "127.0.0.1", cluster.coord.port, req, params=FAST
            )
            assert result.found
            assert (result.nonce, result.hash_value) == (g_min, h_min)
            assert result.nonce >> NB >= 1
            # the coordinator's host verification accepted a rolled win
            assert cluster.coord.stats["results_rejected"] == 0
        finally:
            await cluster.close()

    run(scenario())


# ---------------------------------------------------------------------------
# batched rolling (ISSUE 7): one dispatch sweeps many rolls
# ---------------------------------------------------------------------------

def test_batched_roll_property_pin():
    """Seeded property pin: batched roll rows == the same extranonce
    rolled alone (a batch of one) == midstates derived from
    ``chain.rolled_header`` + hashlib, across random (extranonce_size,
    branch depth, B) combos."""
    import random as _random

    hdr80 = chain.GENESIS_HEADER.pack()
    for seed in range(4):
        rnd = _random.Random(1000 + seed)
        en_size = rnd.choice([1, 2, 4, 8])
        depth = rnd.randrange(0, 5)
        b = rnd.choice([1, 2, 5, 9])
        rng = np.random.RandomState(2000 + seed)
        prefix = rng.bytes(rnd.randrange(1, 90))
        suffix = rng.bytes(rnd.randrange(0, 90))
        branch = tuple(rng.bytes(32) for _ in range(depth))
        cb = chain.CoinbaseTemplate(prefix, suffix, en_size)
        ens = [rnd.randrange(0, 1 << (8 * en_size)) for _ in range(b)]
        batch = merkle.make_extranonce_roll_batch(
            hdr80, prefix, suffix, en_size, branch
        )
        alone = _roll_one(hdr80, prefix, suffix, en_size, branch)
        mids, tails = batch(
            jnp.asarray(np.array([e >> 32 for e in ens], np.uint32)),
            jnp.asarray(np.array([e & 0xFFFFFFFF for e in ens], np.uint32)),
        )
        mids, tails = np.asarray(mids), np.asarray(tails)
        for i, en in enumerate(ens):
            want_hdr = chain.rolled_header(hdr80, cb, branch, en)
            t = ops.header_template(want_hdr.pack())  # hashlib-derived
            s_mid, s_tw = alone(en)
            assert tuple(int(x) for x in mids[i]) == t.midstate, (seed, en)
            assert tuple(int(x) for x in tails[i]) == want_hdr.tail_words()
            assert (np.asarray(s_mid) == mids[i]).all()
            assert (np.asarray(s_tw) == tails[i]).all()


def test_plan_tiles_padding_and_ragged_tail():
    """A dispatch window is decomposed into ≤ rows global-order tiles,
    padded with valid=0 — including the B > remaining-segments ragged
    tail, where the window extends past the domain end."""
    from tpuminter import rolled

    nb, en_size = 8, 1  # domain = 2^16 global indices
    hard_end = (1 << (nb + 8 * en_size)) - 1
    width = rolled.tile_width(nb, 1 << 20)
    assert width == 1 << nb  # segment-capped
    # B=6 window starting 2.5 segments before the domain end: only the
    # remaining segments materialize, the rest is padding
    start = hard_end - (5 << (nb - 1)) + 1  # 2.5 segments left
    plan = rolled.plan_tiles(start, 6 * width, nb, width, 8, hard_end)
    covered = int(plan.valids.sum())
    assert covered == hard_end - start + 1
    real = plan.valids > 0
    assert real.sum() == 3  # 2 full + 1 half segment
    assert (plan.valids[~real] == 0).all()
    # global order, and every tile inside one segment
    gs = plan.goffs[real]
    assert (np.diff(gs.astype(np.int64)) > 0).all()
    for i in np.flatnonzero(real):
        g = start + int(plan.goffs[i])
        en, nonce = chain.split_global(g, nb)
        assert en == (int(plan.en_hi[i]) << 32 | int(plan.en_lo[i]))
        assert nonce == int(plan.bases[i])
        assert nonce + int(plan.valids[i]) <= 1 << nb
    # a window too wide for the row budget raises loudly (unclamped)
    with pytest.raises(ValueError):
        rolled.plan_tiles(0, 20 * width, nb, width, 8, hard_end)


def _drain(gen):
    result = None
    for item in gen:
        if item is not None:
            result = item
    return result


@pytest.mark.parametrize("roll_batch", [1, 2, 8])
def test_jax_miner_rolled_matches_brute_force(ground_truth, roll_batch):
    """Every ``--roll-batch`` size, 1 included, takes the one batched
    tracking sweep and returns brute force's exact Result on found,
    exhausted, and ragged partial-chunk jobs."""
    from tpuminter.jax_worker import JaxMiner

    *_, all_h, h_min, g_min = ground_truth
    lo, hi = (1 << NB) + 100, (3 << NB) + 50
    for target, lower, upper in (
        (h_min, 0, (ENS << NB) - 1),  # found
        (1, 0, (ENS << NB) - 1),      # exhausted
        (1, lo, hi),                  # ragged partial chunk
    ):
        req = _rolled_request(ground_truth, target, lower, upper)
        got = _drain(JaxMiner(batch=512, roll_batch=roll_batch).mine(req))
        span = all_h[lower:upper + 1]  # all_h[g] is index g's pair
        wins = [(h, g) for h, g in span if h <= target]
        if wins:
            h, g = min(wins, key=lambda p: p[1])
            want = (True, g, h, g - lower + 1)
        else:
            h, g = min(span)
            want = (False, g, h, upper - lower + 1)
        assert (got.found, got.nonce, got.hash_value, got.searched) == want, (
            roll_batch, lower, upper)


@pytest.fixture(scope="module")
def candidate_truth(ground_truth):
    """The fixture space's candidates at an 8-bit candidate bar (top
    hash byte zero) — what the fast path surfaces when tests shrink
    ``cand_bits`` to make a CI-sized space contain candidates."""
    *_, all_h, _, _ = ground_truth
    cands = [(h, g) for h, g in all_h if h >> 248 == 0]
    assert len(cands) >= 4  # the seed-0 space has a healthy candidate set
    return cands


@pytest.mark.parametrize("roll_batch", [1, 4, 8])
def test_fast_and_tracking_match_brute_force_winner(
    ground_truth, candidate_truth, roll_batch
):
    """Fast/tracking equivalence regression: on an overlapping
    toy-difficulty rolled job — target = the candidate minimum, so every
    winner clears the candidate bar and both paths are exact — the
    candidate pipeline (`mine_rolled_fast`, TpuMiner's engine), the
    tracking sweep (`mine_rolled_tracking`) and JaxMiner all return brute
    force's (found, nonce, hash) at every roll_batch size."""
    from tpuminter import rolled
    from tpuminter.jax_worker import JaxMiner

    h_c, g_c = min(candidate_truth)
    req = _rolled_request(ground_truth, target=h_c)
    results = {
        "fast": _drain(rolled.mine_rolled_fast(
            req, slab=256, roll_batch=roll_batch, engine="jnp", cand_bits=8)),
        "tracking": _drain(rolled.mine_rolled_tracking(
            req, width_cap=256, roll_batch=roll_batch)),
        "jax_miner": _drain(
            JaxMiner(batch=256, roll_batch=roll_batch).mine(req)),
    }
    for name, r in results.items():
        assert (r.found, r.nonce, r.hash_value) == (True, g_c, h_c), (name, r)
        assert r.nonce >> NB >= 1, name  # the roll actually happened
    # ordered acceptance: everything below the winner was searched. The
    # tracking sweep counts exactly the prefix; the candidate pipeline
    # may additionally count in-flight windows above the win that
    # resolved before it (honest coverage, never less than prefix).
    assert results["tracking"].searched == g_c + 1
    assert results["jax_miner"].searched == g_c + 1
    assert g_c + 1 <= results["fast"].searched <= req.upper + 1


@pytest.mark.parametrize("roll_batch", [1, 4, 8])
def test_fast_exhausted_reports_candidate_min(
    ground_truth, candidate_truth, roll_batch
):
    """Exhausted fast sweeps report the exact range minimum iff a
    candidate surfaced — the global-index candidate bookkeeping agrees
    with brute force at every roll_batch size."""
    from tpuminter import rolled

    req = _rolled_request(ground_truth, target=1)  # unbeatable
    r = _drain(rolled.mine_rolled_fast(
        req, slab=256, roll_batch=roll_batch, engine="jnp", cand_bits=8))
    assert not r.found
    assert (r.hash_value, r.nonce) == min(candidate_truth)
    assert r.searched == ENS << NB
