"""Production pod-path tests on the fake 8-device CPU mesh (VERDICT r2
#3; BASELINE.json:5): the striped candidate sweep's ICI early exit and
exact-lowest contract, and PodMiner end-to-end through the Miner
interface and the real cluster.

Candidate-validity note: the candidate test (top 32 hash bits zero) only
fires for real-difficulty hashes, which CI cannot brute-force — except
for the genesis block, whose known diff-1 winner IS a candidate. Every
found-path test therefore mines windows around the genesis nonce; the
rolled pod path (whose fixtures can't contain candidates) is exercised
on its exhausted path: segment iteration, the on-device roll feeding the
dynamic-header pod sweep, and searched accounting.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuminter import chain
from tpuminter.ops import sha256 as ops
from tpuminter.parallel import build_candidate_sweep, make_mesh
from tpuminter.pod_worker import PodMiner, _biased_cap
from tpuminter.protocol import MIN_UNTRACKED, PowMode, Request
from tpuminter.worker import CpuMiner

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the fake 8-device CPU mesh"
)

GEN = chain.GENESIS_HEADER
TARGET = chain.bits_to_target(GEN.bits)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices()[:8])


@pytest.fixture(scope="module")
def sweep(mesh):
    template = ops.header_template(GEN.pack())
    return build_candidate_sweep(
        mesh, template, slab_per_device=256, n_slabs=4, kernel="jnp"
    )


def _drain(gen):
    result = None
    for item in gen:
        if item is not None:
            result = item
    return result


def test_candidate_sweep_finds_genesis(sweep):
    # span = 8 dev × 4 stripes × 256 = 8192; winner 2500 past start sits
    # in stripe 1 → the or-reduce must stop the pod after stripe 1
    start = GEN.nonce - 2500
    found, first, stripes = sweep(jnp.uint32(start), _biased_cap(TARGET))
    assert int(found) == 1
    assert int(first) == 2500  # offset from start, not an absolute nonce
    assert int(stripes) == 2  # stripes 0 and 1 ran, 2 and 3 never did


def test_candidate_sweep_offset_survives_u32_wrap(sweep):
    """A span that wraps past 2^32: the winner's OFFSET must still be
    exact (absolute-nonce folding would mis-order wrapped candidates —
    the r3 review's wrap bug)."""
    start = (GEN.nonce - 2500) % (1 << 32)
    # place the window so the wrap boundary sits inside the span but
    # below the winner: start near 2^32, winner offset unchanged
    hi_start = (1 << 32) - 1000  # span covers [2^32-1000, 2^32) ∪ [0, 7192)
    found, first, stripes = sweep(jnp.uint32(hi_start), _biased_cap(TARGET))
    # no candidate lives in that window: must be clean, all stripes run
    assert int(found) == 0
    assert int(stripes) == 4
    # and the genesis window still reports the same offset as unwrapped
    found, first, _ = sweep(jnp.uint32(start), _biased_cap(TARGET))
    assert (int(found), int(first)) == (1, 2500)


def test_candidate_sweep_clean_window(sweep):
    # a window with no candidate: all stripes run, nothing found
    found, _, stripes = sweep(jnp.uint32(12345), _biased_cap(TARGET))
    assert int(found) == 0
    assert int(stripes) == 4


def test_pod_miner_finds_genesis(mesh):
    miner = PodMiner(mesh=mesh, slab_per_device=256, n_slabs=2, kernel="jnp")
    req = Request(
        job_id=7, mode=PowMode.TARGET, lower=GEN.nonce - 3000,
        upper=GEN.nonce + 3000, header=GEN.pack(), target=TARGET,
    )
    result = _drain(miner.mine(req))
    assert result.found
    assert result.nonce == GEN.nonce
    assert result.hash_value == GEN.block_hash_int()
    # ordered acceptance: everything below the winner was searched
    assert result.searched >= GEN.nonce - req.lower + 1


def test_pod_miner_exhausted_reports_candidate_min(mesh):
    """Target one below the genesis hash: the genesis nonce is a
    candidate (clears the hash-word-1 cap) but not a winner — the job
    exhausts and the surfaced candidate IS the exact range minimum."""
    miner = PodMiner(mesh=mesh, slab_per_device=256, n_slabs=2, kernel="jnp")
    req = Request(
        job_id=8, mode=PowMode.TARGET, lower=GEN.nonce - 1000,
        upper=GEN.nonce + 1000, header=GEN.pack(),
        target=GEN.block_hash_int() - 1,
    )
    result = _drain(miner.mine(req))
    assert not result.found
    assert (result.nonce, result.hash_value) == (GEN.nonce, GEN.block_hash_int())
    assert result.searched == 2001


def test_pod_miner_exhausted_no_candidates_sentinel(mesh):
    miner = PodMiner(mesh=mesh, slab_per_device=256, n_slabs=2, kernel="jnp")
    req = Request(
        job_id=9, mode=PowMode.TARGET, lower=0, upper=4000,
        header=GEN.pack(), target=1,
    )
    result = _drain(miner.mine(req))
    assert not result.found
    assert result.hash_value == MIN_UNTRACKED
    assert result.searched == 4001


def test_pod_miner_min_matches_cpu(mesh):
    miner = PodMiner(mesh=mesh, slab_per_device=512, n_slabs=2, kernel="jnp")
    req = Request(job_id=3, mode=PowMode.MIN, lower=5, upper=6001, data=b"pod")
    want = _drain(CpuMiner(batch=512).mine(req))
    got = _drain(miner.mine(req))
    assert (got.nonce, got.hash_value) == (want.nonce, want.hash_value)
    assert got.searched == want.searched


def test_pod_miner_rolled_exhausted_path(mesh):
    """Rolled pod job over a candidate-free space: the on-device roll
    feeds the dynamic-header pod sweep per segment; the exhausted Result
    carries the sentinel and exact searched count."""
    rng = np.random.RandomState(5)
    prefix, suffix = rng.bytes(41), rng.bytes(60)
    branch = (rng.bytes(32), rng.bytes(32))
    nb, ens = 11, 3  # 2048-nonce segments, 3 extranonces
    miner = PodMiner(mesh=mesh, slab_per_device=64, n_slabs=2, kernel="jnp")
    req = Request(
        job_id=11, mode=PowMode.TARGET, lower=100,
        upper=(ens << nb) - 50, header=GEN.pack(),
        target=chain.bits_to_target(GEN.bits),
        coinbase_prefix=prefix, coinbase_suffix=suffix,
        extranonce_size=4, branch=branch, nonce_bits=nb,
    )
    result = _drain(miner.mine(req))
    assert not result.found
    assert result.hash_value == MIN_UNTRACKED
    assert result.searched == req.upper - req.lower + 1


def test_pod_miner_easy_target_delegates(mesh):
    """Toy-easy targets are not the candidate regime: PodMiner must
    still return the correct first winner (via the delegate)."""
    import struct

    target = (1 << 250) - 1
    want = None
    prefix = GEN.pack()[:76]
    for n in range(0, 5000):
        h = chain.hash_to_int(chain.dsha256(prefix + struct.pack("<I", n)))
        if h <= target:
            want = (n, h)
            break
    assert want is not None
    miner = PodMiner(mesh=mesh, slab_per_device=256, n_slabs=2, kernel="jnp")
    req = Request(job_id=4, mode=PowMode.TARGET, lower=0, upper=5000,
                  header=GEN.pack(), target=target)
    result = _drain(miner.mine(req))
    assert result.found
    assert (result.nonce, result.hash_value) == want


def test_pod_miner_through_cluster(mesh):
    """The role layer drives a whole slice: one PodMiner Joins the real
    coordinator and mines the genesis window end-to-end."""
    from tests.test_e2e import FAST, Cluster, run
    from tpuminter.client import submit

    async def scenario():
        cluster = await Cluster.create(
            n_miners=1, chunk_size=16384,
            miner_factory=lambda: PodMiner(
                mesh=mesh, slab_per_device=256, n_slabs=2, kernel="jnp"
            ),
        )
        try:
            req = Request(
                job_id=77, mode=PowMode.TARGET, lower=GEN.nonce - 3000,
                upper=GEN.nonce + 3000, header=GEN.pack(), target=TARGET,
            )
            result = await submit(
                "127.0.0.1", cluster.coord.port, req, params=FAST
            )
            assert result.found
            assert result.nonce == GEN.nonce
            assert cluster.coord.stats["results_rejected"] == 0
        finally:
            await cluster.close()

    run(scenario())


def test_pod_miner_scrypt_sharded(mesh):
    """SCRYPT sharded over the mesh: pod result ≡ CpuMiner, winner and
    exhausted-minimum both, including a ragged tail below one pod span."""
    import struct

    hdr = GEN.pack()
    prefix = hdr[:76]
    upper = 8 * 64 + 37  # one full pod span (8 dev × 64) + ragged tail
    all_h = [
        (chain.hash_to_int(chain.scrypt_hash(prefix + struct.pack("<I", n))), n)
        for n in range(upper + 1)
    ]
    h_min, n_min = min(all_h)
    miner = PodMiner(mesh=mesh, slab_per_device=256, n_slabs=2, kernel="jnp")

    req = Request(job_id=21, mode=PowMode.SCRYPT, lower=0, upper=upper,
                  header=hdr, target=h_min)
    result = _drain(miner.mine(req))
    assert result.found
    assert (result.nonce, result.hash_value) == (n_min, h_min)

    req = Request(job_id=22, mode=PowMode.SCRYPT, lower=0, upper=upper,
                  header=hdr, target=1)
    result = _drain(miner.mine(req))
    assert not result.found
    assert (result.hash_value, result.nonce) == (h_min, n_min)
    assert result.searched == upper + 1


def _rolled_fixture(nb=10, ens=4, seed=5):
    rng = np.random.RandomState(seed)
    prefix, suffix = rng.bytes(41), rng.bytes(60)
    branch = (rng.bytes(32), rng.bytes(32))
    import struct

    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    all_h = []
    for en in range(ens):
        p76 = chain.rolled_header(GEN.pack(), cb, branch, en).pack()[:76]
        for n in range(1 << nb):
            h = chain.hash_to_int(chain.dsha256(p76 + struct.pack("<I", n)))
            all_h.append((h, (en << nb) | n))
    return prefix, suffix, branch, all_h


@pytest.mark.parametrize("roll_batch", [1, 6, 8])
def test_pod_miner_rolled_ragged_range_matches_brute_force(mesh, roll_batch):
    """Every `--roll-batch` size, 1 included, takes the one batched pod
    sweep (device-major row stripes through build_rolled_sweep) and
    returns brute force's Result on a ragged range — the exact first
    winner, and the exact candidate minimum with full coverage when
    nothing wins — at the jnp engine's 8-bit candidate bar."""
    prefix, suffix, branch, all_h = _rolled_fixture()
    nb, ens = 10, 4
    lower, upper = 100, (ens << nb) - 50
    h_c, g_c = min(
        (h, g) for h, g in all_h if lower <= g <= upper and h >> 248 == 0)
    miner = PodMiner(
        mesh=mesh, slab_per_device=64, n_slabs=2, kernel="jnp",
        roll_batch=roll_batch,
    )
    miner._cand_bits = 8
    for target in (h_c, 1):
        req = Request(
            job_id=21, mode=PowMode.TARGET, lower=lower, upper=upper,
            header=GEN.pack(), target=target,
            coinbase_prefix=prefix, coinbase_suffix=suffix,
            extranonce_size=4, branch=branch, nonce_bits=nb,
        )
        r = _drain(miner.mine(req))
        assert (r.found, r.nonce, r.hash_value) == (target == h_c, g_c, h_c)
        if r.found:
            assert g_c - lower + 1 <= r.searched <= upper - lower + 1
        else:
            assert r.searched == upper - lower + 1


def test_pod_miner_rolled_batched_finds_exact_first_winner(mesh):
    """The batched pod sweep's found path at a CI-reachable candidate
    bar (the jnp engine's `cand_bits` test seam, 8 bits): the winner is
    the exact lowest GLOBAL winning index — the stripe-interleaved
    early exit never skips a lower row — and the exhausted path
    surfaces the exact candidate minimum."""
    prefix, suffix, branch, all_h = _rolled_fixture()
    nb, ens = 10, 4
    cands = [(h, g) for h, g in all_h if h >> 248 == 0]
    h_c, g_c = min(cands)
    mk = lambda target, jid: Request(
        job_id=jid, mode=PowMode.TARGET, lower=0, upper=(ens << nb) - 1,
        header=GEN.pack(), target=target, coinbase_prefix=prefix,
        coinbase_suffix=suffix, extranonce_size=4, branch=branch,
        nonce_bits=nb,
    )
    miner = PodMiner(
        mesh=mesh, slab_per_device=128, n_slabs=2, kernel="jnp",
        roll_batch=6,
    )
    miner._cand_bits = 8
    r = _drain(miner.mine(mk(h_c, 22)))
    assert r.found and (r.nonce, r.hash_value) == (g_c, h_c)
    assert r.nonce >> nb >= 1  # the roll actually happened
    r2 = _drain(miner.mine(mk(1, 23)))
    assert not r2.found and (r2.hash_value, r2.nonce) == (h_c, g_c)
    assert r2.searched == ens << nb


# -- one four-chip host (a v5e 2x2) as one pod worker -------------------------


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(jax.devices()[:4])


@pytest.fixture(scope="module")
def sweep4(mesh4):
    template = ops.header_template(GEN.pack())
    return build_candidate_sweep(
        mesh4, template, slab_per_device=256, n_slabs=4, kernel="jnp"
    )


def test_pod_candidate_sweep_has_its_own_program_name(sweep4):
    """A device trace tells the pod sweep from every other pod program
    (each a shard_map of a function named ``per_device``) by this name."""
    text = sweep4.lower(jnp.uint32(0), _biased_cap(TARGET)).as_text()
    assert "module @jit_pod_candidate_sweep " in text


@pytest.mark.parametrize("stripe", [0, 2])
def test_pod_sweep_stops_after_the_stripe_of_its_first_candidate(sweep4, stripe):
    # stripes of 4 chips x 256 nonces: the genesis nonce lies 300 nonces
    # into `stripe`, in chip 1's slab, and no candidate lies below it
    offset = stripe * 4 * 256 + 256 + 44
    found, first, stripes = sweep4(
        jnp.uint32(GEN.nonce - offset), _biased_cap(TARGET)
    )
    assert (int(found), int(first), int(stripes)) == (1, offset, stripe + 1)


def _first_share(lo: int, hi: int):
    """The first nonce in [lo, hi] whose genesis header meets the
    diff-1 target, and its hash, by hashlib alone."""
    import hashlib
    import struct

    prefix = GEN.pack()[:76]
    for n in range(lo, hi + 1):
        digest = hashlib.sha256(
            hashlib.sha256(prefix + struct.pack("<I", n)).digest()
        ).digest()
        h = int.from_bytes(digest, "little")
        if h <= TARGET:
            return n, h
    return None


@pytest.fixture(scope="module")
def pod4(mesh4):
    return PodMiner(mesh=mesh4, slab_per_device=256, n_slabs=4, kernel="jnp")


@pytest.mark.parametrize("below", [3000, 1500, 0])
def test_pod_miner_on_four_chips_answers_genesis_jobs(pod4, below):
    """Jobs shaped as the genesis benchmark's: [lo, 2^32 - 1] with the
    genesis nonce at most 3000 above lo."""
    lo = GEN.nonce - below
    want = _first_share(lo, GEN.nonce)
    assert want == (GEN.nonce, GEN.block_hash_int())
    req = Request(
        job_id=30 + below, mode=PowMode.TARGET, lower=lo, upper=(1 << 32) - 1,
        header=GEN.pack(), target=TARGET,
    )
    result = _drain(pod4.mine(req))
    assert result.found and (result.nonce, result.hash_value) == want
    assert result.searched >= below + 1


@pytest.fixture(scope="module")
def striped_pod(mesh4):
    """Four-chip PodMiners by ``slab_per_device`` (two slabs a chip a
    call), each with its TARGET sweep built by a genesis job."""
    built = {}

    def get(slab):
        if slab not in built:
            miner = PodMiner(
                mesh=mesh4, slab_per_device=slab, n_slabs=2, kernel="jnp"
            )
            req = Request(
                job_id=40, mode=PowMode.TARGET, lower=GEN.nonce - 100,
                upper=GEN.nonce + 100, header=GEN.pack(), target=TARGET,
            )
            result = _drain(miner.mine(req))
            assert result.found and result.nonce == GEN.nonce
            built[slab] = miner
        return built[slab]

    return get


@pytest.mark.parametrize("slab, stripe, k, r", [
    (256, 256, 0, 37),    # chip 0's piece of the job's first slab
    (256, 256, 1, 200),   # chip 3's piece of the second slab
    (256, 256, 3, 130),   # chip 2's piece of the fourth slab
    (250, 1000, 1, 300),  # 250 does not split over 4 chips: whole slabs
])
def test_pod_miner_sweeps_one_slab_a_stripe(striped_pod, slab, stripe, k, r):
    """The TARGET sweep the miner built stripes one slab across the
    four chips, ``slab / 4`` nonces a chip: a winner ``k`` slabs and
    ``r`` nonces past the start stops the call after stripe ``k``, with
    its exact offset. A slab that does not split evenly keeps one whole
    slab a chip, so a stripe is four slabs."""
    sweep = striped_pod(slab)._sweep_static
    offset = k * stripe + r
    found, first, stripes = sweep(
        jnp.uint32(GEN.nonce - offset), _biased_cap(TARGET)
    )
    assert (int(found), int(first), int(stripes)) == (1, offset, k + 1)


@pytest.mark.parametrize("slab, stripes", [(256, 8), (250, 2)])
def test_pod_miner_stripe_plan_covers_the_dispatched_span(
        striped_pod, slab, stripes):
    """Whatever the stripes, a call still covers ``n_dev × n_slabs ×
    slab_per_device`` nonces, the span the search dispatches and the
    coordinator is told: the span's last nonce is swept in its last
    stripe, and a window that ends just below the winner runs every
    stripe and finds nothing."""
    miner = striped_pod(slab)
    span = 4 * 2 * slab
    assert miner.pod_span == miner.span == span
    cap = _biased_cap(TARGET)
    found, first, ran = miner._sweep_static(
        jnp.uint32(GEN.nonce - (span - 1)), cap)
    assert (int(found), int(first), int(ran)) == (1, span - 1, stripes)
    found, _, ran = miner._sweep_static(jnp.uint32(GEN.nonce - span), cap)
    assert (int(found), int(ran)) == (0, stripes)
