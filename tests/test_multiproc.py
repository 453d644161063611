"""Multi-process sharded coordinator (ISSUE 19): the seam frame codec's
round-trip/rejection properties, the config guards, and the 2-process
end-to-end gates.

The drills are the tier-1 acceptance the issue names, run on real OS
processes behind ONE UDP port: zero duplicate answers and zero lost
miners across the process seam, a kill -9 + recovery whose re-submitted
LIVE job lands on a FOREIGN shard process and settles exactly once
through the cross-shard rebind registry, and one tenant's token bucket
enforced fleet-wide while its submissions alternate across processes.
The gates are deterministic invariants, not throughput: the
procs-throughput curve needs a multi-core host."""

import os
import sys

import pytest

sys.path.insert(
    0,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
    ),
)

import loadgen  # noqa: E402  (scripts/ is not a package)

from tpuminter.multiproc import MultiProcCoordinator  # noqa: E402
from tpuminter.protocol import (  # noqa: E402
    ProtocolError,
    SEAM_CKEY_MAX,
    decode_seam,
    encode_seam_answer,
    encode_seam_bind,
    encode_seam_fwd,
    encode_seam_quota,
    encode_seam_rebind,
)

from tests.test_e2e import run  # noqa: E402


# ---------------------------------------------------------------------------
# the seam frame codec (pure)
# ---------------------------------------------------------------------------

def test_seam_frames_round_trip():
    """Every seam dialect survives encode → decode bit-exact, including
    the miss flag and a ckey at the size limit."""
    assert decode_seam(
        encode_seam_fwd(("10.1.2.3", 65535), b"\x01payload")
    ) == ("fwd", ("10.1.2.3", 65535), b"\x01payload")

    big_ckey = "k" * SEAM_CKEY_MAX
    assert decode_seam(encode_seam_bind(7, big_ckey, 2**63)) == (
        "bind", 7, big_ckey, 2**63,
    )
    assert decode_seam(
        encode_seam_rebind(1, 0xDEADBEEF, "tenant-a", 42)
    ) == ("rebind", 1, 0xDEADBEEF, "tenant-a", 42)
    assert decode_seam(
        encode_seam_answer(0xDEADBEEF, 42, b"\x7b\x7d")
    ) == ("answer", False, 0xDEADBEEF, 42, b"\x7b\x7d")
    assert decode_seam(
        encode_seam_answer(3, 9, b"", miss=True)
    ) == ("answer", True, 3, 9, b"")
    assert decode_seam(encode_seam_quota(0, "tenant-b", 10**9)) == (
        "quota", 0, "tenant-b", 10**9,
    )


def test_seam_frames_reject_corruption_and_bad_fields():
    """The seam is loss-tolerant, so the decoder must refuse (never
    misread) every damaged frame: flipped bits, truncation, unknown
    tags, and out-of-contract fields at encode time."""
    frame = bytearray(encode_seam_rebind(0, 11, "tenant", 5))
    frame[len(frame) // 2] ^= 0x40
    with pytest.raises(ProtocolError):
        decode_seam(bytes(frame))  # CRC catches the flip
    good = encode_seam_bind(1, "k", 2)
    for cut in (0, 1, len(good) - 1):
        with pytest.raises(ProtocolError):
            decode_seam(good[:cut])
    with pytest.raises(ProtocolError):
        decode_seam(b"\xee" + good[1:])  # unknown tag

    with pytest.raises(ProtocolError):
        encode_seam_bind(1, "", 2)  # empty ckey
    with pytest.raises(ProtocolError):
        encode_seam_bind(1, "k" * (SEAM_CKEY_MAX + 1), 2)
    with pytest.raises(ProtocolError):
        encode_seam_answer(1, 2, b"data", miss=True)  # miss carries none
    with pytest.raises(ProtocolError):
        encode_seam_fwd(("::1", 9), b"")  # IPv4 only on the seam
    with pytest.raises(ProtocolError):
        encode_seam_fwd(("127.0.0.1", 1 << 16), b"")
    with pytest.raises(ProtocolError):
        encode_seam_rebind(256, 1, "k", 1)  # origin is one byte


# ---------------------------------------------------------------------------
# config guards
# ---------------------------------------------------------------------------

def test_multiproc_rejects_bad_configs():
    async def scenario():
        with pytest.raises(ValueError):
            await MultiProcCoordinator.create(procs=0)
        # process mode owns the whole port: in-process loops/threads
        # on top of it would double-shard the same peers
        with pytest.raises(ValueError):
            await loadgen.make_coordinator(procs=2, loops=2)
        with pytest.raises(ValueError):
            await loadgen.make_coordinator(procs=2, threaded=True)

    run(scenario())


# ---------------------------------------------------------------------------
# the 2-process gates (ISSUE 19 acceptance)
# ---------------------------------------------------------------------------

def test_two_proc_smoke_rebind_and_quota_drills():
    """The tier-1 2-process gate: a fleet-8 burst across 2 shard
    processes sustains with zero duplicate answers, zero lost miners,
    and both processes answering stats over the control seam; then the
    kill -9 rebind drill settles its cross-process re-submit exactly
    once THROUGH the rebind registry (honored >= 1 proves the answer
    crossed the seam rather than being re-mined); then the shared-quota
    drill holds one tenant to its fleet-wide budget while alternating
    shards."""
    metrics = run(loadgen.run_multiproc(8, 4, 1.2, procs=2), timeout=180.0)
    assert loadgen.multiproc_check(metrics) == [], metrics
    assert metrics["procs"] == 2
    assert metrics["dup_answers"] == 0
    assert metrics["miners_lost"] == 0
    assert metrics["shards_replied"] == 2
    # the kernel steers on this image's cBPF; if attach ever regresses
    # to the userspace fallback the seam must still deliver (fwd path),
    # so steer_kernel is recorded but not load-bearing for correctness
    assert metrics["steer_kernel"] in (True, False)
    assert metrics["rebind_settled"] == 1
    assert metrics["rebind_seam_honored"] >= 1
    assert metrics["quota_admitted"] <= metrics["quota_burst"] + 1
    assert metrics["quota_foreign_debits"] > 0, (
        "quota drill alternated shards but no bucket ever saw a "
        "foreign debit — the gossip seam is dark"
    )


def test_one_proc_mode_is_the_degenerate_case():
    """procs=1 must behave exactly like a plain coordinator behind the
    process supervisor — no steering (one socket), no drills needed,
    full throughput path intact: the baseline that seam overhead is
    measured against."""
    metrics = run(
        loadgen.run_multiproc(6, 2, 0.9, procs=1, drills=False),
        timeout=120.0,
    )
    assert metrics["procs"] == 1
    assert metrics["results_per_s"] > 0
    assert metrics["dup_answers"] == 0
    assert metrics["miners_lost"] == 0
    assert metrics["shards_replied"] == 1
    assert metrics["steer_kernel"] is False
