"""The extranonce roll takes its template as operands: one compiled
program per coinbase/branch layout serves every job of that layout.

Seeded random Stratum templates in two layouts — coinb1 42 B / coinb2
70 B (the extranonce hole in the coinbase's first 64-byte block) and
coinb1 70 B / coinb2 87 B (the hole in its second block) — at merkle
branch depths 0, 1 and 12 are rolled on the device and pinned bit for
bit to ``chain.rolled_header`` and ``hashlib``; fresh templates of one
layout add no compile; the rolled miners record one ``tpuminter.roll``
span per window, planned up to the chunk's end; and ``PodMiner`` on a
four-device mesh answers two fresh templates in a row as brute force
does.
"""

import contextlib
import dataclasses
import hashlib
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuminter import chain, rolled
from tpuminter.ops import merkle
from tpuminter.ops import sha256 as ops
from tpuminter.protocol import PowMode, Request

LAYOUTS = {"hole_in_block0": (42, 70), "hole_in_block1": (70, 87)}


def _template(seed, prefix_len, suffix_len, depth):
    rng = np.random.RandomState(seed)
    header = rng.bytes(80)
    return (header, rng.bytes(prefix_len), rng.bytes(suffix_len),
            tuple(rng.bytes(32) for _ in range(depth)))


def _roll(template, en_size, ens):
    header, prefix, suffix, branch = template
    mids, tails = merkle.roll_batch_deduped(
        *merkle.roll_template(header, prefix, suffix, en_size, branch),
        np.array([e >> 32 for e in ens], np.uint32),
        np.array([e & 0xFFFFFFFF for e in ens], np.uint32),
    )
    return np.asarray(mids), np.asarray(tails)


def _dsha(data):
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


#: extranonces that differ only in their high word, or only in their low
HIGH_LOW = [0, 1, 0xFFFFFFFF, 1 << 32, (1 << 32) | 1, (7 << 32) | 1,
            0xDEADBEEF_00000001, 0xFFFFFFFF_FFFFFFFF]


@pytest.mark.parametrize("depth", [0, 1, 12])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_roll_is_bit_equal_to_the_host_header(layout, depth):
    """Each row's (midstate, tail words) is the rolled header's, as
    ``chain.rolled_header`` builds it with hashlib; and the double
    SHA-256 of that header at a nonce, hashed from the rolled row, is
    hashlib's."""
    prefix_len, suffix_len = LAYOUTS[layout]
    template = _template(1000 + depth + prefix_len, prefix_len, suffix_len, depth)
    header, prefix, suffix, branch = template
    mids, tails = _roll(template, 8, HIGH_LOW)
    cb = chain.CoinbaseTemplate(prefix, suffix, 8)
    nonces = jnp.asarray(np.array([0, 0x1D2C3B4A], np.uint32))
    for i, en in enumerate(HIGH_LOW):
        coinbase = prefix + en.to_bytes(8, "little") + suffix
        root = _dsha(coinbase)
        for sib in branch:
            root = _dsha(root + sib)
        want = chain.rolled_header(header, cb, branch, en)
        assert want.merkle_root == root
        t = ops.header_template(want.pack())
        assert tuple(int(w) for w in mids[i]) == t.midstate, en
        assert tuple(int(w) for w in tails[i]) == want.tail_words(), en
        digests = np.asarray(ops.header_digest_dyn(mids[i], tails[i], nonces))
        for d, nonce in zip(digests, np.asarray(nonces)):
            raw = want.pack()[:76] + struct.pack("<I", int(nonce))
            assert struct.pack(">8I", *(int(w) for w in d)) == _dsha(raw)
    # rows that differ only in the high word roll to different headers
    assert (mids[1] != mids[4]).any() and (mids[4] != mids[5]).any()


def test_stratum_extranonce2_size_is_bit_equal():
    """The benchmark's deployment: a 4-byte extranonce2 after a 70-byte
    coinb1 (extranonce1 included), an 87-byte coinb2, 12 levels."""
    template = _template(7, 70, 87, 12)
    header, prefix, suffix, branch = template
    ens = [0, 1, 2, 0x7FFFFFFF, 0xFFFFFFFF]
    mids, tails = _roll(template, 4, ens)
    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    for i, en in enumerate(ens):
        want = chain.rolled_header(header, cb, branch, en)
        assert tuple(int(w) for w in mids[i]) == ops.header_template(want.pack()).midstate
        assert tuple(int(w) for w in tails[i]) == want.tail_words()


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_fresh_templates_of_one_layout_cost_one_compile(layout):
    """Five templates with fresh coinbase, branch and header bytes, the
    layout alike: the roll's program is compiled once, for the first."""
    prefix_len, suffix_len = LAYOUTS[layout]
    merkle.extranonce_roll.clear_cache()
    for seed in range(5):
        template = _template(50 + seed, prefix_len, suffix_len, 12)
        _roll(template, 4, [seed, seed + 1, 9])
        assert merkle.extranonce_roll._cache_size() == 1
    # another layout is another program
    _roll(_template(60, prefix_len + 1, suffix_len, 12), 4, [0, 1, 9])
    assert merkle.extranonce_roll._cache_size() == 2


def test_deduped_roll_is_one_program_per_shape():
    """The dedup's fork runs inside the roll's own program: a window's
    rows, padding included, come back from one device call."""
    merkle.extranonce_roll.clear_cache()
    for seed in range(3):
        header, prefix, suffix, branch = _template(80 + seed, 70, 87, 12)
        layout, words = merkle.roll_template(header, prefix, suffix, 4, branch)
        en_hi = np.zeros(8, np.uint32)
        en_lo = np.array([seed + 1] * 4 + [0] * 4, np.uint32)
        got_m, got_t = merkle.roll_batch_deduped(layout, words, en_hi, en_lo)
        # every row rolled on its own, no dedup
        want_m, want_t = merkle.extranonce_roll(
            layout, *words, jnp.asarray(en_hi), jnp.asarray(en_lo),
            jnp.arange(8, dtype=jnp.int32))
        assert (np.asarray(got_m) == np.asarray(want_m)).all()
        assert (np.asarray(got_t) == np.asarray(want_t)).all()
    # one deduped shape (2 uniques forked to 8 rows) and one of every
    # row on its own (8 uniques)
    assert merkle.extranonce_roll._cache_size() == 2


def _rolled_request(template, nb, lower, upper, target, job_id=1, chunk_id=0):
    header, prefix, suffix, branch = template
    return Request(
        job_id=job_id, mode=PowMode.TARGET, lower=lower, upper=upper,
        header=header, target=target, coinbase_prefix=prefix,
        coinbase_suffix=suffix, extranonce_size=4, branch=branch,
        nonce_bits=nb, chunk_id=chunk_id,
    )


def _brute(req):
    """(hash, global index) of every index of ``req``, by hashlib."""
    cb = chain.CoinbaseTemplate(req.coinbase_prefix, req.coinbase_suffix, 4)
    out = []
    for g in range(req.lower, req.upper + 1):
        en, nonce = chain.split_global(g, req.nonce_bits)
        p76 = chain.rolled_header(req.header, cb, req.branch, en).pack()[:76]
        out.append((chain.hash_to_int(_dsha(p76 + struct.pack("<I", nonce))), g))
    return out


def _drain(gen):
    result = None
    for item in gen:
        if item is not None:
            result = item
    return result


def test_a_chunk_shorter_than_the_window_plans_only_its_own_rows(monkeypatch):
    """A window is planned up to the chunk's upper: the rows past it are
    padding, and each roll span carries the chunk's ids and the indices
    its sweep covers."""
    recorded = []

    def span(name, **ids):
        recorded.append((name, ids))
        return contextlib.nullcontext()

    monkeypatch.setattr(rolled, "span", span)
    nb = 9
    template = _template(3, 70, 87, 12)
    # a 2-row chunk of a 4 x 256-index window, inside one segment; at
    # the production candidate bar no candidate re-issues a remainder
    req = _rolled_request(template, nb, 5 << nb, (5 << nb) + 511, 1,
                          job_id=9, chunk_id=4)
    got = _drain(rolled.mine_rolled_fast(
        req, slab=256, roll_batch=4, engine="jnp", cand_bits=32))
    assert got.searched == 512
    assert recorded == [("tpuminter.roll", {"job": 9, "chunk": 4, "nonces": 512})]


def test_mine_rolled_fast_answers_fresh_templates_in_a_row():
    """One miner process, three jobs, each on a fresh template of the
    Stratum layout: each is answered as brute force answers it, and the
    roll's programs are those of the layout's shapes (a window's unique
    extranonces, 1 or 2 at 4 rows), not one for each template."""
    nb = 8
    merkle.extranonce_roll.clear_cache()
    for seed in range(3):
        req = _rolled_request(_template(200 + seed, 70, 87, 12), nb,
                              0, (2 << nb) - 1, 1)
        space = _brute(req)
        cands = sorted(p for p in space if p[0] >> 248 == 0)
        target = cands[len(cands) // 2][0] if cands else 1
        got = _drain(rolled.mine_rolled_fast(
            dataclasses.replace(req, target=target),
            slab=256, roll_batch=4, engine="jnp", cand_bits=8))
        win = min((g for h, g in space if h <= target), default=None)
        assert got.found == (win is not None)
        if win is not None:
            assert (got.nonce, got.hash_value) == (win, space[win][0])
    assert 1 <= merkle.extranonce_roll._cache_size() <= 2


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs four virtual CPU devices")
def test_pod_miner_answers_two_fresh_templates_in_a_row():
    """``PodMiner._mine_rolled`` on a four-device mesh, two jobs in a
    row on two fresh templates of one layout: the first winner of each,
    as brute force gives it; the roll's programs are those of the
    layout's shapes (1, 2 or 4 unique extranonces at 8 rows), not one
    for each template."""
    from tpuminter.parallel import make_mesh
    from tpuminter.pod_worker import PodMiner

    nb = 10
    miner = PodMiner(mesh=make_mesh(jax.devices()[:4]), slab_per_device=128,
                     n_slabs=2, kernel="jnp", roll_batch=4)
    miner._cand_bits = 8
    merkle.extranonce_roll.clear_cache()
    for seed in (300, 301):
        req = _rolled_request(_template(seed, 70, 87, 12), nb, 100,
                              (3 << nb) - 50, 1, job_id=seed)
        space = _brute(req)
        cands = sorted(p for p in space if p[0] >> 248 == 0)
        assert cands
        target = cands[len(cands) // 2][0]
        got = _drain(miner.mine(dataclasses.replace(req, target=target)))
        h, g = min((g, h) for h, g in space if h <= target)[::-1]
        assert (got.found, got.nonce, got.hash_value) == (True, g, h)
    assert 1 <= merkle.extranonce_roll._cache_size() <= 3
