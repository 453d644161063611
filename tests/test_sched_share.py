"""Shared-compression scheduling pins (ISSUE 16): the AsicBoost-grade
layer — one message schedule serving every colliding rolled row, the
only body the rolled sweeps have — returns what a hashlib scan of the
same rolled headers returns, across random (en_size, branch depth, B,
width), ragged tails, candidate-bearing windows, and tie-breaking on
the exact tracking fold.

Seeded-deterministic versions run everywhere; tests/test_properties.py
carries the hypothesis mirrors of the same invariants.
"""

import struct

import numpy as np
import jax.numpy as jnp
import pytest

from tpuminter import chain, rolled
from tpuminter.ops import merkle
from tpuminter.ops import sha256 as ops
from tpuminter.ops import symbolic as sym
from tpuminter.protocol import PowMode, Request

SEED = 1604  # arxiv 1604.00575
_UMAX = 0xFFFFFFFF

#: one coinbase template for every sweep-level pin: rows are real rolled
#: headers, so hashlib can score them
_CB_RNG = np.random.RandomState(SEED)
_PREFIX, _SUFFIX = _CB_RNG.bytes(41), _CB_RNG.bytes(60)
_BRANCH = (_CB_RNG.bytes(32), _CB_RNG.bytes(32))
_HDR80 = chain.GENESIS_HEADER.pack()


def _drain(gen):
    result = None
    for item in gen:
        if item is not None:
            result = item
    return result


def _rolled_rows(rng, b, width, ragged=True):
    """``b`` roll rows of random 4-byte extranonces — the sweep's
    ``(mids, tails, bases, valids, goffs)`` plus the extranonces that
    the hashlib reference re-rolls on the host."""
    ens = rng.randint(0, 1 << 32, b, dtype=np.uint32)
    mids, tails = merkle.roll_batch_deduped(
        *merkle.roll_template(_HDR80, _PREFIX, _SUFFIX, 4, _BRANCH),
        np.zeros(b, np.uint32), ens)
    bases = rng.randint(0, 1 << 20, b).astype(np.uint32)
    if ragged:
        valids = np.where(
            np.arange(b) < b - 2, np.uint32(width),
            rng.randint(0, width + 1, b).astype(np.uint32),
        )
    else:
        valids = np.full(b, width, np.uint32)
    goffs = (np.arange(b, dtype=np.uint64) * width).astype(np.uint32)
    args = (mids, tails, jnp.asarray(bases), jnp.asarray(valids),
            jnp.asarray(goffs))
    return ens, bases, valids, goffs, args


def _is_candidate(h: int, cap: int, cand_bits: int) -> bool:
    """The candidate bar on the 256-bit hash value: top ``cand_bits``
    bits zero, plus the hash-word-1 cap at the production 32."""
    if cand_bits == 32:
        return h >> 224 == 0 and (h >> 192) & _UMAX <= cap
    return h >> (256 - cand_bits) == 0


def _hashlib_sweep(ens, bases, valids, goffs, cap, cand_bits):
    """``[found, first_global_off]`` of a batched candidate sweep,
    computed with hashlib over the host-rolled headers."""
    cb = chain.CoinbaseTemplate(_PREFIX, _SUFFIX, 4)
    first = _UMAX
    for en, base, valid, goff in zip(ens, bases, valids, goffs):
        p76 = chain.rolled_header(_HDR80, cb, _BRANCH, int(en)).pack()[:76]
        for c in range(int(valid)):
            nonce = struct.pack("<I", int(base) + c)
            if _is_candidate(chain.hash_to_int(chain.dsha256(p76 + nonce)),
                             cap, cand_bits):
                first = min(first, int(goff) + c)
                break
    return [int(first != _UMAX), first]


# ---------------------------------------------------------------------------
# the truncated shared-schedule hash vs the full digest
# ---------------------------------------------------------------------------

def test_header_e60_e61_matches_full_digest_words():
    """The two digest words the candidate test reads are recovered
    exactly from (e60, e61): word 7 = H0[7] + e60, word 6 =
    DIGEST6_BIAS + e61 — over random dynamic headers and nonces."""
    rng = np.random.RandomState(SEED)
    for _ in range(4):
        mid = jnp.asarray(rng.randint(0, 1 << 32, 8, dtype=np.uint32))
        tw = jnp.asarray(rng.randint(0, 1 << 32, 3, dtype=np.uint32))
        nonces = jnp.asarray(rng.randint(0, 1 << 32, 64, dtype=np.uint32))
        digests = np.asarray(ops.header_digest_dyn(mid, tw, nonces))
        e60, e61 = ops.header_e60_e61_dyn(mid, tw, nonces)
        w7 = (np.uint32(ops.SHA256_H0[7]) + np.asarray(e60))
        w6 = (np.uint32(sym.DIGEST6_BIAS) + np.asarray(e61))
        assert np.array_equal(digests[:, 7], w7)
        assert np.array_equal(digests[:, 6], w6)


def test_prepare_hdr_finisher_matches_hash_sym():
    """prepare_hdr + hash_prepared_e60_e61 ≡ hash_sym_e60_e61 — on
    traced u32 inputs AND on all-int inputs (where both must const-fold
    to plain Python ints: the Pallas kernels' baked-template regime)."""
    rng = np.random.RandomState(SEED + 1)
    mid = [jnp.uint32(x) for x in rng.randint(0, 1 << 32, 8, dtype=np.uint32)]
    t0, t1, t2 = (jnp.uint32(x) for x in rng.randint(0, 1 << 32, 3,
                                                     dtype=np.uint32))
    nonces = jnp.asarray(rng.randint(0, 1 << 32, 32, dtype=np.uint32))
    block = [t0, t1, t2, ops.byteswap32(nonces), *ops.HEADER_TAIL_PAD]
    want = sym.hash_sym_e60_e61(mid, [block], (), 0, 0)
    prep = sym.prepare_hdr(mid, t0, t1, t2)
    got = sym.hash_prepared_e60_e61(prep, nonces)
    assert np.array_equal(np.asarray(want[0]), np.asarray(got[0]))
    assert np.array_equal(np.asarray(want[1]), np.asarray(got[1]))

    imid = [int(x) for x in np.asarray(jnp.stack(mid))]
    it = [int(t0), int(t1), int(t2)]
    for n in (0, 1, 0xDEADBEEF):
        iblock = [*it, int(np.asarray(ops.byteswap32(jnp.uint32(n)))),
                  *ops.HEADER_TAIL_PAD]
        want = sym.hash_sym_e60_e61(imid, [iblock], (), 0, 0)
        got = sym.hash_prepared_e60_e61(
            sym.prepare_hdr(imid, *it), n
        )
        assert isinstance(got[0], int) and isinstance(got[1], int)
        assert got == want


# ---------------------------------------------------------------------------
# the batched sweep ≡ a hashlib scan of the same rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cand_bits", [8, 32])
def test_batched_sweep_matches_hashlib_scan(cand_bits):
    """_jnp_batched_candidate_sweep ≡ a hashlib scan of the same rolled
    headers across random rows, ragged valids, and both candidate-test
    arms."""
    rng = np.random.RandomState(SEED + cand_bits)
    for b, width in ((4, 64), (8, 64), (3, 256)):
        ens, bases, valids, goffs, args = _rolled_rows(rng, b, width)
        cap = int(rng.randint(0, 1 << 32))
        got = np.asarray(rolled._jnp_batched_candidate_sweep(
            *args, jnp.uint32(cap), width, cand_bits))
        want = _hashlib_sweep(ens, bases, valids, goffs, cap, cand_bits)
        assert got.tolist() == want, (b, width)


def test_batched_sweep_matches_hashlib_on_candidate_bearing_window():
    """Equality must hold where it matters: windows that actually
    surface a candidate (found=1, exact first global offset)."""
    rng = np.random.RandomState(SEED + 2)
    width, b, cand_bits = 64, 4, 4  # 4-bit bar: hits are plentiful
    hits = 0
    for _ in range(8):
        ens, bases, valids, goffs, args = _rolled_rows(
            rng, b, width, ragged=False)
        got = np.asarray(rolled._jnp_batched_candidate_sweep(
            *args, jnp.uint32(_UMAX), width, cand_bits))
        want = _hashlib_sweep(ens, bases, valids, goffs, _UMAX, cand_bits)
        assert got.tolist() == want
        hits += want[0]
    assert hits > 0  # the pin exercised the found arm, not just misses


# ---------------------------------------------------------------------------
# the roll dedup: gathered uniques ≡ rolling every row
# ---------------------------------------------------------------------------

def _roll_each_row(template, en_hi, en_lo):
    """The roll of every row on its own: no dedup, row i forked from
    extranonce i."""
    layout, words = template
    return merkle.extranonce_roll(
        layout, *words, jnp.asarray(en_hi), jnp.asarray(en_lo),
        jnp.arange(len(en_lo), dtype=jnp.int32))


def test_roll_batch_deduped_bit_equal():
    """roll_batch_deduped ≡ the plain batched roll — duplicate-heavy,
    all-unique, and all-identical extranonce row sets."""
    rng = np.random.RandomState(SEED + 3)
    prefix, suffix = rng.bytes(41), rng.bytes(60)
    branch = (rng.bytes(32), rng.bytes(32))
    hdr80 = chain.GENESIS_HEADER.pack()
    template = merkle.roll_template(hdr80, prefix, suffix, 4, branch)
    cases = [
        np.array([5, 5, 5, 6, 6, 7, 5, 6], np.uint32),   # dup-heavy
        np.arange(8, dtype=np.uint32),                    # all unique
        np.full(8, 9, np.uint32),                         # one extranonce
        np.array([2, 2, 2], np.uint32),                   # non-pow2 rows
    ]
    for en_lo in cases:
        en_hi = np.zeros_like(en_lo)
        want_m, want_t = _roll_each_row(template, en_hi, en_lo)
        got_m, got_t = merkle.roll_batch_deduped(*template, en_hi, en_lo)
        assert np.array_equal(np.asarray(want_m), np.asarray(got_m))
        assert np.array_equal(np.asarray(want_t), np.asarray(got_t))


def test_roll_batch_deduped_wide_extranonce():
    """The (hi, lo) u32 pair reassembles into the dedup key correctly:
    rows equal in lo but different in hi must NOT collapse."""
    rng = np.random.RandomState(SEED + 4)
    prefix, suffix = rng.bytes(41), rng.bytes(60)
    hdr80 = chain.GENESIS_HEADER.pack()
    template = merkle.roll_template(hdr80, prefix, suffix, 8, ())
    en_hi = np.array([0, 1, 0, 1], np.uint32)
    en_lo = np.array([7, 7, 7, 7], np.uint32)
    want_m, want_t = _roll_each_row(template, en_hi, en_lo)
    got_m, got_t = merkle.roll_batch_deduped(*template, en_hi, en_lo)
    assert np.array_equal(np.asarray(want_m), np.asarray(got_m))
    assert np.array_equal(np.asarray(want_t), np.asarray(got_t))
    assert not np.array_equal(np.asarray(want_m)[0], np.asarray(want_m)[1])


# ---------------------------------------------------------------------------
# end-to-end: the rolled miners ≡ brute force over the job's space
# ---------------------------------------------------------------------------

def _random_rolled_request(rng, nb, en_size, depth, target):
    prefix = rng.bytes(int(rng.randint(2, 64)))
    suffix = rng.bytes(int(rng.randint(2, 64)))
    branch = tuple(rng.bytes(32) for _ in range(depth))
    return Request(
        job_id=1, mode=PowMode.TARGET, lower=0, upper=(4 << nb) - 1,
        header=chain.GENESIS_HEADER.pack(), target=target,
        coinbase_prefix=prefix, coinbase_suffix=suffix,
        extranonce_size=en_size, branch=branch, nonce_bits=nb,
    )


def _brute(req):
    """(hash, global index) of every index in the job, by hashlib."""
    cb = chain.CoinbaseTemplate(
        req.coinbase_prefix, req.coinbase_suffix, req.extranonce_size)
    out = []
    for en in range((req.upper + 1) >> req.nonce_bits):
        p76 = chain.rolled_header(req.header, cb, req.branch, en).pack()[:76]
        for n in range(1 << req.nonce_bits):
            h = chain.hash_to_int(chain.dsha256(p76 + struct.pack("<I", n)))
            out.append((h, (en << req.nonce_bits) | n))
    return out


@pytest.mark.parametrize("nb,en_size,depth", [(8, 4, 2), (9, 8, 0), (8, 4, 3)])
def test_mine_rolled_fast_matches_brute_force(nb, en_size, depth):
    """mine_rolled_fast returns the brute-force answer across random
    jobs varying (nonce_bits, extranonce size, branch depth): the first
    winner when the target sits below the 8-bit candidate bar, and the
    exact candidate minimum with full coverage when nothing wins."""
    import dataclasses

    rng = np.random.RandomState(SEED + nb + en_size + depth)
    req = _random_rolled_request(rng, nb, en_size, depth, 1)
    space = _brute(req)
    cands = sorted(p for p in space if p[0] >> 248 == 0)
    assert cands  # an 8-bit bar over ≥ 1024 hashes surfaces some
    kw = dict(slab=256, roll_batch=4, engine="jnp", cand_bits=8)
    # a mid-ranked candidate's hash: beaten by several indices
    target = cands[len(cands) // 2][0]
    found = _drain(rolled.mine_rolled_fast(
        dataclasses.replace(req, target=target), **kw))
    g_win = min(g for h, g in space if h <= target)
    assert (found.found, found.nonce, found.hash_value) == (
        True, g_win, space[g_win][0])
    assert g_win + 1 <= found.searched <= req.upper + 1
    exhausted = _drain(rolled.mine_rolled_fast(req, **kw))
    assert (exhausted.found, exhausted.hash_value, exhausted.nonce) == (
        False, *cands[0])
    assert exhausted.searched == req.upper + 1


def test_mine_rolled_tracking_matches_brute_force_with_dup_ties():
    """The exact tracking fold through the roll dedup — on a job whose
    windows span whole segments (every row of a dispatch shares one
    extranonce, the dedup's maximal case) the first-winner AND
    lexicographic-min results, tie-breaks included, are brute force's."""
    rng = np.random.RandomState(SEED + 5)
    req = _random_rolled_request(rng, 8, 4, 2, target=1)
    kw = dict(width_cap=256, roll_batch=4)
    got = _drain(rolled.mine_rolled_tracking(req, **kw))
    h_min, g_min = min(_brute(req))
    assert (got.found, got.nonce, got.hash_value, got.searched) == (
        False, g_min, h_min, req.upper + 1)
    # found regime too (winner surfaced through the deduped rows)
    req2 = _random_rolled_request(rng, 8, 4, 1, target=1 << 252)
    got = _drain(rolled.mine_rolled_tracking(req2, **kw))
    h_win, g_win = next(p for p in _brute(req2) if p[0] <= req2.target)
    assert (got.found, got.nonce, got.hash_value, got.searched) == (
        True, g_win, h_win, g_win + 1)
