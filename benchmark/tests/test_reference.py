"""The plain references: they agree with hashlib, find what a brute
force finds, and reject an answer with one nonce bit flipped."""

import hashlib
import os
import random

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# XLA:CPU's fusion pass takes exponential time on unrolled SHA rounds
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_disable_hlo_passes=fusion"


#: Bitcoin block 0 and its published nonce, a diff-1 share
GENESIS = (
    "01000000" + "00" * 32
    + "3ba3edfd7a7b12b27ac72c3e67768f617fc81bc3888a51323a9fb8aa4b1e5e4a"
    + "29ab5f49" + "ffff001d" + "1dac2b7c"
)
GENESIS_NONCE = 2083236893


def _genesis_job(lo, hi):
    return {"kind": "target", "header": GENESIS, "bits": "0x1d00ffff", "lo": lo, "hi": hi}


def _rolled_job(rng, bits="0x2000ffff", lo=0, span=600):
    return {
        "kind": "rolled", "bits": bits, "extranonce_size": 4,
        "header": "00000020" + rng.randbytes(72).hex() + "00000000",
        "prefix": rng.randbytes(42).hex(), "suffix": rng.randbytes(70).hex(),
        "branch": [rng.randbytes(32).hex() for _ in range(12)],
        "lo": lo, "hi": lo + span - 1,
    }


def _brute_first(btc, job):
    target = btc.target_of(job)
    for g in range(job["lo"], job["hi"] + 1):
        h = btc.hash_value(btc.header_at(job, g))
        if h <= target:
            return {"found": True, "index": g, "hash": h}
    return {"found": False}


def test_host_compress_matches_hashlib(btc):
    rng = random.Random(1)
    for _ in range(4):
        block = rng.randbytes(64)
        # a 55-byte message fits one block with its padding
        msg = rng.randbytes(55)
        padded = msg + b"\x80" + (55 * 8).to_bytes(8, "big")
        words = btc.compress_host(btc.IV, padded)
        assert b"".join(w.to_bytes(4, "big") for w in words) == hashlib.sha256(msg).digest()
        assert len(btc.compress_host(btc.IV, block)) == 8


def test_expected_sweeps_for_the_genesis_share(btc, tmp_path):
    jobs = [_genesis_job(GENESIS_NONCE - d, 2**32 - 1) for d in (5, 1500, 0)]
    jobs.append(_genesis_job(GENESIS_NONCE + 1, GENESIS_NONCE + 300))
    assert _brute_first(btc, jobs[-1]) == {"found": False}
    want = btc.expected(jobs, str(tmp_path), dict(os.environ))
    assert [w["found"] for w in want] == [True, True, True, False]
    assert all(w["index"] == GENESIS_NONCE and btc.check(j, w) is None
               for j, w in zip(jobs, want[:3]))


def test_shared_sweeps_give_each_job_its_first_share(btc):
    """Jobs that overlap, nest, abut or stand apart on two headers, at a
    target met about once in 256 nonces, against brute force."""
    import jax  # noqa: F401

    tile = 1 << 12
    sweep = btc.make_sweep(tile)
    rng = random.Random(9)
    headers = [rng.randbytes(80).hex() for _ in range(2)]
    jobs = []
    for _ in range(14):
        lo = rng.randrange(3000)
        jobs.append({"kind": "target", "header": rng.choice(headers), "bits": "0x2000ffff",
                     "lo": lo, "hi": lo + rng.choice((0, 5, 40, 300, 900))})
    got = btc.first_shares(sweep, tile, jobs)
    want = [_brute_first(btc, job) for job in jobs]
    assert [w.get("index") for w in want] == got
    assert sum(g is not None for g in got) >= 5


def test_device_sweep_finds_the_first_share(btc):
    import jax  # noqa: F401

    tile = 1 << 12
    sweep = btc.make_sweep(tile)
    rng = random.Random(7)
    for seed in range(3):
        job = _rolled_job(random.Random(seed), lo=(seed << 32) + rng.randrange(1000))
        want = _brute_first(btc, job)
        got = btc.first_share(sweep, tile, btc.target_of(job), btc._segments(job))
        assert (got is None) == (not want["found"])
        if got is not None:
            assert got == want["index"]
    # and on the genesis header at difficulty 1
    job = _genesis_job(GENESIS_NONCE - 3000, GENESIS_NONCE + 10)
    assert btc.first_share(sweep, tile, btc.target_of(job), btc._segments(job)) == GENESIS_NONCE


def test_btc_rejects_a_flipped_nonce_bit(btc, tmp_path):
    job = _rolled_job(random.Random(3))
    ans = _brute_first(btc, job)
    assert ans["found"] and btc.check(job, ans) is None
    bad = dict(ans, index=ans["index"] ^ 1)
    assert btc.check(job, bad) is not None
    gen = _genesis_job(GENESIS_NONCE - 2000, 2**32 - 1)
    good = btc.expected([gen], str(tmp_path), dict(os.environ))[0]
    assert btc.check(gen, good) is None
    assert btc.check(gen, dict(good, index=good["index"] ^ 1)) is not None


def test_ltc_rejects_a_flipped_nonce_bit(ltc):
    job = {"kind": "scrypt", "bits": "0x1a010000", "header": random.Random(5).randbytes(80).hex(),
           "lo": 100, "hi": 131}
    _, (h, nonce) = ltc._scan(job["header"], job["lo"], job["hi"], ltc.target_of(job))
    ans = {"found": False, "index": nonce, "hash": h}
    assert ltc.check(job, ans) is None
    assert ltc.check(job, dict(ans, index=nonce ^ 1)) is not None


@pytest.mark.parametrize("found", [False, True])
def test_ltc_expected_is_the_range_answer(ltc, tmp_path, found):
    header = random.Random(11).randbytes(80).hex()
    bits = "0x2000ffff" if found else "0x1a010000"
    job = {"kind": "scrypt", "bits": bits, "header": header, "lo": 7, "hi": 70}
    hashes = [(ltc.hash_value(ltc.header_at(job, n)), n) for n in range(7, 71)]
    (got,) = ltc.expected([job], str(tmp_path), dict(os.environ))
    winners = [n for h, n in hashes if h <= ltc.target_of(job)]
    if found:
        assert winners and got["found"] and got["index"] == winners[0]
    else:
        h, n = min(hashes)
        assert got == {"found": False, "index": n, "hash": h}
