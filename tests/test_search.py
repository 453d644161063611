"""CandidateSearch driver logic, on a scripted fake device.

The fake reproduces the real kernel's contract exactly — first
candidate offset in the swept range, early exit, pad-lane quirk — so
the pipelining/ordering/remainder logic is pinned without a TPU
(SURVEY.md §4's own-the-seam test idea, applied to the device seam).
"""

import random
from collections import Counter

import pytest

from tpuminter.search import CandidateSearch


class FakeChip:
    """Emulates pallas_search_candidates + host verify.

    ``candidates``: sorted nonces whose digest word 7 "is zero".
    ``winners``: subset that also beats the target.
    ``chained``: honour the ``after`` handle as the kernel's ``stop``
    operand does — no work when it reports found or skipped; otherwise
    ignore it, as the sweeps that do not chain do.
    """

    def __init__(self, candidates, winners, chained=False):
        self.candidates = sorted(candidates)
        self.winners = set(winners)
        assert self.winners <= set(self.candidates)
        self.chained = chained
        self.sweeps = []  # (base, n) log, dispatch order
        self.skipped = []  # (base, n) of the sweeps that did no work
        self.verifies = []

    def sweep(self, base, n, after):
        self.sweeps.append((base, n))
        assert len(self.sweeps) < 10_000, "the search does not converge"
        if self.chained and after is not None and (after[0] or after[2]):
            self.skipped.append((base, n))
            return (0, 0, 1)
        hit = next(
            (c for c in self.candidates if base <= c < base + n), None
        )
        return (0, 0, 0) if hit is None else (1, hit - base, 0)

    def resolve(self, handle):
        return handle

    def verify(self, nonce):
        self.verifies.append(nonce)
        assert nonce in self.candidates, "verified a non-candidate"
        # fake hash: winners tiny, losers just above-target
        return nonce in self.winners, (1 << 200) if nonce in self.winners else (1 << 230)

    def search(self, lower, upper, slab=100, depth=2):
        s = CandidateSearch(
            self.sweep, self.resolve, self.verify, lower, upper,
            slab=slab, depth=depth,
        )
        for _ in s.events():
            pass
        return s.outcome


def test_clean_exhaustion_counts_everything():
    chip = FakeChip([], [])
    out = chip.search(0, 999)
    assert not out.found and out.nonce is None
    assert out.searched == 1000
    assert chip.verifies == []


def test_true_win_is_exact_and_prunes_later_work():
    chip = FakeChip([350], [350])
    out = chip.search(0, 999)
    assert out.found and out.nonce == 350
    assert out.hash_value == 1 << 200
    # pruning: after the win resolves, no new ranges above it are
    # issued — only calls already in flight (≤ depth of them) may sit
    # above the winning nonce
    above = [base for base, _ in chip.sweeps if base > 350]
    assert len(above) <= 2  # the pipeline depth


def test_false_positive_reissues_remainder():
    chip = FakeChip([50], [])
    out = chip.search(0, 299)
    assert not out.found
    # the remainder [51, 99] was searched despite the early exit —
    # dispatched as a full slab (single compiled kernel size)
    assert (51, 100) in chip.sweeps
    assert out.searched == 300
    assert out.candidates == [(50, 1 << 230)]


def test_win_in_remainder_beats_later_range_win():
    # A[0,99] false-positives at 50; B[100,199] wins at 150 and resolves
    # BEFORE the remainder, which holds the true lowest winner at 70.
    chip = FakeChip([50, 70, 150], [70, 150])
    out = chip.search(0, 999, slab=100, depth=2)
    assert out.found and out.nonce == 70


def test_later_win_held_until_remainder_clears():
    # remainder has no candidate: B's win at 150 must still only be
    # reported after the remainder sweep confirms [51,99] is clean.
    chip = FakeChip([50, 150], [150])
    out = chip.search(0, 999, slab=100, depth=2)
    assert out.found and out.nonce == 150
    assert (51, 100) in chip.sweeps  # remainder was actually swept


def test_exhausted_best_is_min_candidate():
    chip = FakeChip([20, 80], [])
    out = chip.search(0, 99, slab=10)
    assert not out.found
    assert out.best == (1 << 230, 20)
    assert out.searched == 100


def test_pad_lane_hit_past_range_is_clean_cover():
    class PadChip(FakeChip):
        def sweep(self, base, n, after):
            self.sweeps.append((base, n))
            return (1, n + 7, 0)  # fired past the real range

    chip = PadChip([], [])
    out = chip.search(0, 999)
    assert not out.found and out.searched == 1000
    assert chip.verifies == []


@pytest.mark.parametrize("seed, chained", [
    *(pytest.param(s, False, id=str(s)) for s in range(20)),
    *(pytest.param(s, True, id=f"{s}-chained") for s in range(20)),
])
def test_randomized_matches_bruteforce(seed, chained):
    rng = random.Random(seed)
    lower, upper = 0, rng.randrange(200, 2000)
    space = range(lower, upper + 1)
    candidates = sorted(rng.sample(space, rng.randrange(0, 12)))
    winners = [c for c in candidates if rng.random() < 0.4]
    chip = FakeChip(candidates, winners, chained=chained)
    slab = rng.choice([37, 100, 256, 4096])
    depth = rng.choice([1, 2, 3])
    out = chip.search(lower, upper, slab=slab, depth=depth)
    if winners:
        assert out.found and out.nonce == min(winners)
    else:
        assert not out.found
        assert out.searched == upper - lower + 1
        if candidates:
            assert out.best == (1 << 230, min(candidates))
    # no cascade: each candidate skips at most the sweeps behind it
    assert len(chip.skipped) <= depth * len(candidates)


def test_chained_win_skips_the_sweep_behind_it():
    chip = FakeChip([350], [350], chained=True)
    out = chip.search(0, 999, slab=100, depth=2)
    assert out.found and (out.nonce, out.hash_value) == (350, 1 << 200)
    # brute force sweeps [0, 350] and stops there
    assert out.searched == 351
    # the slab dispatched behind the winner's skipped on the device, and
    # nothing above it was issued
    assert chip.skipped == [(400, 100)]
    assert [b for b, _ in chip.sweeps] == [0, 100, 200, 300, 400]


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_chained_false_positive_reissues_the_skipped_range(depth):
    # 150 is a false positive; the sweeps behind it skip, their ranges
    # go back to the queue and the search still finds the lowest winner
    chip = FakeChip([150, 420, 610], [420, 610], chained=True)
    out = chip.search(0, 999, slab=100, depth=depth)
    assert out.found and out.nonce == 420
    assert out.candidates[0] == (150, 1 << 230)
    # every nonce below the winner was swept by a sweep that did work
    worked = Counter(chip.sweeps) - Counter(chip.skipped)
    covered = set()
    for b, n in worked:
        covered.update(range(b, b + n))
    assert set(range(421)) <= covered
    # at most depth - 1 sweeps were in flight behind each candidate
    assert len(chip.skipped) <= 2 * (depth - 1)
    if depth > 1:
        assert (200, 100) in chip.skipped
        assert [b for b, _ in chip.sweeps].count(200) == 2  # re-issued


# -- pipeline_spans: the generic double-buffer (MIN/scrypt/exact-min) ----


def test_pipeline_spans_keeps_depth_in_flight():
    from tpuminter.search import pipeline_spans

    dispatched = []

    def dispatch(s):
        dispatched.append(s)
        return f"h{s}"

    gen = pipeline_spans(range(5), dispatch, depth=2)
    first = next(gen)
    # at the first yield exactly one EXTRA dispatch is outstanding:
    # the consumer blocks on span 0 while span 1 computes
    assert first == (0, "h0")
    assert dispatched == [0, 1]
    rest = list(gen)
    assert [first] + rest == [(i, f"h{i}") for i in range(5)]
    assert dispatched == list(range(5))


def test_pipeline_spans_depth_one_is_the_synchronous_loop():
    from tpuminter.search import pipeline_spans

    dispatched = []
    gen = pipeline_spans(range(3), lambda s: dispatched.append(s) or s, 1)
    assert next(gen) == (0, 0)
    assert dispatched == [0]  # nothing speculative at depth 1
    assert list(gen) == [(1, 1), (2, 2)]


def test_pipeline_spans_abandon_leaves_inflight_unresolved():
    """The Cancel/early-exit contract: a consumer that stops leaves at
    most ``depth`` handles dispatched beyond what it consumed, and the
    generator never touches them again (JAX async arrays are simply
    garbage-collected — same as CandidateSearch's abandoned handles)."""
    from tpuminter.search import pipeline_spans

    dispatched = []
    gen = pipeline_spans(range(100), lambda s: dispatched.append(s) or s, 3)
    for span, handle in gen:
        assert span == handle
        if span == 4:
            gen.close()  # winner found / Cancel landed
            break
    # consumed 0..4; speculative dispatches are bounded by depth - 1
    # beyond the last yielded span (span 4 was yielded right after
    # span 4 + depth - 1 = 6 was dispatched)
    assert dispatched == list(range(7))


def test_pipeline_spans_rejects_bad_depth():
    from tpuminter.search import pipeline_spans

    with pytest.raises(ValueError):
        list(pipeline_spans([1], lambda s: s, 0))


def test_global_domain_search_crosses_segment_boundaries():
    """The rolled generalization (ISSUE 7): one CandidateSearch over a
    >2^32 GLOBAL index domain, slabs crossing extranonce boundaries —
    same exact-lowest-winner contract, bookkeeping keyed by global
    index. (The batched sweep itself is pinned in test_extranonce; this
    pins the driver's queueing/ordering over the wide domain.)"""
    base_g = 1 << 34  # far beyond the 32-bit nonce space
    chip = FakeChip(
        candidates=[base_g + 150, base_g + 9050],
        winners=[base_g + 9050],
    )
    s = CandidateSearch(
        chip.sweep, chip.resolve, chip.verify,
        base_g - 1000, base_g + 20_000,
        slab=4096, depth=2, domain=1 << 40,
    )
    for _ in s.events():
        pass
    out = s.outcome
    assert out.found and out.nonce == base_g + 9050
    assert out.candidates[0] == (base_g + 150, 1 << 230)
    # the false positive's remainder was re-issued before later ranges
    assert chip.verifies == [base_g + 150, base_g + 9050]
    # without the widened domain, the same range is rejected loudly
    with pytest.raises(ValueError):
        CandidateSearch(
            chip.sweep, chip.resolve, chip.verify,
            base_g - 1000, base_g + 20_000, slab=4096,
        )
