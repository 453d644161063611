"""Property-based tests (hypothesis) over the framework's pure seams.

SURVEY.md §4's load-bearing test idea is "own the transport seam, inject
faults at it"; the asyncio suite (tests/test_lsp.py, tests/test_fuzz.py)
does that with real sockets and timers. This module pushes the same
invariants through *deterministic, timer-free* state-machine drives so
hypothesis can shrink any violation to a minimal schedule:

- the frame codec round-trips arbitrary frames and rejects every
  single-byte corruption (CRC-32 catches all ≤32-bit bursts);
- two :class:`~tpuminter.lsp.connection.ConnState` machines wired
  through an in-memory channel deliver every written message exactly
  once, in order, under arbitrary drop/duplicate/reorder schedules and
  arbitrary message sizes (fragmentation boundaries included);
- ``chain.rolled_segments`` tiles any global-index range exactly;
- the app-protocol codec round-trips every message type, rolled
  Requests included;
- the coordinator journal's record stream obeys the same corruption
  contract as the bundled frame codec (corruption/truncation can only
  look like loss of a suffix) and its replay is idempotent.
  (tests/test_recovery.py carries deterministic seeded versions of the
  same properties, since this image lacks hypothesis.)
"""

import random
from collections import deque

import pytest

# optional test extra (pyproject [test]); a loud skip beats a collection
# error when the image lacks it
pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st

from tpuminter import chain
from tpuminter.lsp.connection import FRAGMENT_SIZE, ConnState
from tpuminter.lsp.message import (
    MAX_PAYLOAD,
    Frame,
    MsgType,
    decode,
    decode_all,
    encode,
)
from tpuminter.lsp.params import Params
from tpuminter.protocol import (
    Assign,
    Cancel,
    Join,
    PowMode,
    Refuse,
    Request,
    Result,
    Setup,
    decode_msg,
    encode_msg,
)

# ---------------------------------------------------------------------------
# frame codec
# ---------------------------------------------------------------------------

frames = st.builds(
    Frame,
    type=st.sampled_from(list(MsgType)),
    conn_id=st.integers(0, 2**32 - 1),
    seq=st.integers(0, 2**32 - 1),
    payload=st.binary(max_size=MAX_PAYLOAD),
)


@given(frames)
def test_codec_roundtrip(frame):
    assert decode(encode(frame)) == frame


@given(frames, st.data())
def test_codec_rejects_any_single_byte_corruption(frame, data):
    wire = bytearray(encode(frame))
    i = data.draw(st.integers(0, len(wire) - 1))
    flip = data.draw(st.integers(1, 255))
    wire[i] ^= flip
    assert decode(bytes(wire)) is None


@given(frames, st.integers(0, MAX_PAYLOAD + 14))
def test_codec_rejects_any_truncation(frame, keep):
    wire = encode(frame)
    if keep < len(wire):
        assert decode(wire[:keep]) is None


# ---------------------------------------------------------------------------
# bundled datagrams (decode_all): several frames per datagram
# ---------------------------------------------------------------------------

@settings(max_examples=80)
@given(st.lists(frames, min_size=1, max_size=5))
def test_bundle_roundtrip(frs):
    wire = b"".join(bytes(encode(f)) for f in frs)
    assert list(decode_all(wire)) == frs


@settings(max_examples=80)
@given(st.lists(frames, min_size=1, max_size=4), st.data())
def test_bundle_corruption_yields_only_a_clean_prefix(frs, data):
    """A 1-byte flip anywhere in a bundled datagram may unframe
    everything after it, but what DOES decode must be an exact prefix
    of the original frames — corruption can only look like loss, never
    like different frames (CRC-32 per frame)."""
    wire = bytearray(b"".join(bytes(encode(f)) for f in frs))
    i = data.draw(st.integers(0, len(wire) - 1))
    wire[i] ^= data.draw(st.integers(1, 255))
    got = list(decode_all(bytes(wire)))
    assert len(got) < len(frs) or got != frs  # the flip cost something
    assert got == frs[: len(got)]


@settings(max_examples=80)
@given(st.lists(frames, min_size=1, max_size=4), st.data())
def test_bundle_truncation_yields_only_a_clean_prefix(frs, data):
    wire = b"".join(bytes(encode(f)) for f in frs)
    keep = data.draw(st.integers(0, len(wire) - 1))
    got = list(decode_all(wire[:keep]))
    assert len(got) < len(frs)
    assert got == frs[: len(got)]


# ---------------------------------------------------------------------------
# ConnState pair under hostile frame schedules (timer-free model drive)
# ---------------------------------------------------------------------------

#: Message sizes that cross every fragmentation boundary.
_SIZES = st.one_of(
    st.integers(0, 64),
    st.sampled_from(
        [FRAGMENT_SIZE - 1, FRAGMENT_SIZE, FRAGMENT_SIZE + 1,
         2 * FRAGMENT_SIZE, 2 * FRAGMENT_SIZE + 1, 3500]
    ),
)


def _payload(size: int, seed: int) -> bytes:
    return random.Random(seed).randbytes(size)


@settings(max_examples=60, deadline=None)
@given(
    msgs_a=st.lists(st.tuples(_SIZES, st.integers(0, 2**16)), max_size=8),
    msgs_b=st.lists(st.tuples(_SIZES, st.integers(0, 2**16)), max_size=8),
    window=st.integers(1, 8),
    max_backoff=st.integers(0, 3),
    drop=st.floats(0.0, 0.5),
    dup=st.floats(0.0, 0.3),
    reorder=st.floats(0.0, 0.3),
    seed=st.integers(0, 2**32),
)
def test_connstate_exactly_once_in_order_under_faults(
    msgs_a, msgs_b, window, max_backoff, drop, dup, reorder, seed
):
    """Exactly-once in-order delivery under hostile schedules — now
    including the COALESCED-ACK machine: acks only leave via
    ``flush_acks`` (driven at arbitrary model-chosen points + the
    on_epoch backstop), one cumulative frame may cover many DATA
    frames, and SACK payload words cover the out-of-order tail. The
    final conservation check pins the coalescing accounting: every
    received DATA frame is acknowledged by exactly one flushed ack
    datagram or rides a coalesced one."""
    rng = random.Random(seed)
    params = Params(
        epoch_limit=10**9,  # liveness is not under test; loss must not fire
        epoch_millis=1,
        window_size=window,
        max_backoff_interval=max_backoff,
        max_unacked_messages=window,
    )
    channel = deque()  # (dest_name, Frame) in flight
    recv = {"a": [], "b": []}
    data_frames_rx = {"a": 0, "b": 0}  # DATA frames handed to on_frame

    def make(name, peer_name):
        return ConnState(
            conn_id=7,
            params=params,
            send_frame=lambda f, d=peer_name: channel.append((d, f)),
            deliver=recv[name].append,
            on_lost=lambda reason: (_ for _ in ()).throw(
                AssertionError(f"conn lost during model drive: {reason}")
            ),
        )

    conns = {}
    conns["a"] = make("a", "b")
    conns["b"] = make("b", "a")

    def feed(dest, frame):
        if frame.type == MsgType.DATA:
            data_frames_rx[dest] += 1
        conns[dest].on_frame(frame)

    sent_a = [_payload(s, sd) for s, sd in msgs_a]
    sent_b = [_payload(s, sd) for s, sd in msgs_b]
    # per-side write order is the delivery contract; the rng interleaves
    # WHICH side writes next, never the order within a side
    todo = {"a": deque(sent_a), "b": deque(sent_b)}

    def pump_one_faulty():
        dest, frame = channel.popleft()
        r = rng.random()
        if r < drop:
            return
        if r < drop + dup:
            feed(dest, frame)
            feed(dest, frame)
            return
        if r < drop + dup + reorder and channel:
            channel.append((dest, frame))  # overtaken by everything queued
            return
        feed(dest, frame)

    # Phase 1 — hostile: interleave writes, faulty delivery, ack
    # flushes at arbitrary points, epochs.
    steps = 0
    while todo["a"] or todo["b"] or channel:
        steps += 1
        assert steps < 100_000
        act = rng.random()
        sides = [s for s in "ab" if todo[s]]
        if sides and act < 0.3:
            side = rng.choice(sides)
            conns[side].write(todo[side].popleft())
        elif channel and act < 0.75:
            pump_one_faulty()
        elif act < 0.85:
            conns[rng.choice("ab")].flush_acks()
        else:
            conns[rng.choice("ab")].on_epoch()

    # Phase 2 — drain faithfully: every queued frame delivered, epochs
    # tick so retransmit backoff elapses and pending acks flush.
    # Quiesce = nothing in flight.
    for _ in range(10_000):
        while channel:
            dest, frame = channel.popleft()
            feed(dest, frame)
        if not conns["a"].in_flight and not conns["b"].in_flight:
            if not conns["a"]._pending and not conns["b"]._pending:
                if not channel:
                    break
        conns["a"].on_epoch()
        conns["b"].on_epoch()
    else:
        raise AssertionError("model drive failed to quiesce")

    assert recv["b"] == sent_a
    assert recv["a"] == sent_b
    assert not conns["a"].lost and not conns["b"].lost
    for side in "ab":
        conn = conns[side]
        # coalescing conservation: after a final flush every DATA frame
        # this side ever received (duplicates included) was covered by
        # exactly one flushed ack emission or coalesced into one
        conn.flush_acks()
        assert not conn.acks_pending
        assert conn.acks_sent + conn.acks_coalesced == data_frames_rx[side]


# ---------------------------------------------------------------------------
# rolled-segment arithmetic
# ---------------------------------------------------------------------------

@given(
    nonce_bits=st.integers(1, 32),
    en_lo=st.integers(0, 1000),
    en_span=st.integers(0, 6),
    data=st.data(),
)
def test_rolled_segments_tile_the_range_exactly(nonce_bits, en_lo, en_span, data):
    mask = (1 << nonce_bits) - 1
    lo_off = data.draw(st.integers(0, mask))
    hi_off = data.draw(st.integers(0, mask))
    lower = (en_lo << nonce_bits) | lo_off
    upper = ((en_lo + en_span) << nonce_bits) | hi_off
    if upper < lower:
        upper = lower
    segs = list(chain.rolled_segments(lower, upper, nonce_bits))
    # segments are contiguous, cover [lower, upper] exactly, and each
    # (en, base, n_lo, n_hi) is internally consistent
    expect = lower
    for en, base, n_lo, n_hi in segs:
        assert base == en << nonce_bits
        assert 0 <= n_lo <= n_hi <= mask
        assert base | n_lo == expect
        expect = (base | n_hi) + 1
    assert expect == upper + 1


@given(
    nonce_bits=st.integers(1, 32),
    e0=st.integers(0, 10**6),
    count=st.integers(1, 4096),
)
def test_roll_span_is_exactly_count_whole_segments(nonce_bits, e0, count):
    """The RollAssign expansion (ISSUE 14): ``roll_span(e0, count)`` is
    exactly ``count`` WHOLE extranonce segments — aligned at both ends
    and tiled by ``rolled_segments`` with full nonce sweeps. The
    coordinator's carve and the worker's expansion share this one
    function; any disagreement double-counts the range ledger.
    (tests/test_roll_budget.py carries a deterministic seeded mirror,
    since this image lacks hypothesis.)"""
    lower, upper = chain.roll_span(e0, count, nonce_bits)
    mask = (1 << nonce_bits) - 1
    assert lower == e0 << nonce_bits
    assert lower & mask == 0 and (upper + 1) & mask == 0
    assert upper - lower + 1 == count << nonce_bits
    segs = list(chain.rolled_segments(lower, upper, nonce_bits))
    assert [en for en, _, _, _ in segs] == list(range(e0, e0 + count))
    assert all(n_lo == 0 and n_hi == mask for _, _, n_lo, n_hi in segs)


# ---------------------------------------------------------------------------
# app-protocol codec
# ---------------------------------------------------------------------------

_GENESIS80 = chain.GENESIS_HEADER.pack()

#: Durable client identities (protocol.Request.client_key): empty =
#: anonymous, else an opaque token that must round-trip the codec.
_client_keys = st.one_of(
    st.just(""), st.text(min_size=1, max_size=24)
)

plain_requests = st.builds(
    Request,
    job_id=st.integers(0, 2**31),
    mode=st.just(PowMode.TARGET),
    lower=st.integers(0, 1000),
    upper=st.integers(1000, 2**32 - 1),
    header=st.just(_GENESIS80),
    target=st.integers(1, 2**256 - 1),
    chunk_id=st.integers(0, 2**31),
    client_key=_client_keys,
)

min_requests = st.builds(
    Request,
    job_id=st.integers(0, 2**31),
    mode=st.just(PowMode.MIN),
    lower=st.integers(0, 1000),
    upper=st.integers(1000, 2**64 - 1),
    data=st.binary(max_size=64),
    client_key=_client_keys,
)

rolled_requests = st.builds(
    Request,
    job_id=st.integers(0, 2**31),
    mode=st.just(PowMode.TARGET),
    lower=st.just(0),
    upper=st.integers(0, 2**32 - 1),
    header=st.just(_GENESIS80),
    target=st.integers(1, 2**256 - 1),
    coinbase_prefix=st.binary(min_size=1, max_size=300),
    coinbase_suffix=st.binary(max_size=300),
    extranonce_size=st.integers(1, 4),
    branch=st.lists(st.binary(min_size=32, max_size=32), max_size=13).map(tuple),
)

messages = st.one_of(
    st.builds(
        Join,
        backend=st.text(max_size=16),
        lanes=st.integers(1, 2**20),
        span=st.integers(0, 2**32),
    ),
    plain_requests,
    min_requests,
    rolled_requests,
    st.builds(
        Result,
        job_id=st.integers(0, 2**31),
        mode=st.sampled_from([PowMode.MIN, PowMode.TARGET, PowMode.SCRYPT]),
        nonce=st.integers(0, 2**64 - 1),
        hash_value=st.integers(0, 2**256 - 1),
        found=st.booleans(),
        searched=st.integers(0, 2**64 - 1),
        chunk_id=st.integers(0, 2**31),
    ),
    plain_requests.map(Setup),
    rolled_requests.map(Setup),
    st.builds(
        Assign,
        job_id=st.integers(0, 2**31),
        chunk_id=st.integers(0, 2**31),
        lower=st.integers(0, 2**32 - 1),
        upper=st.integers(0, 2**64 - 1),
    ),
    st.builds(
        Refuse, job_id=st.integers(0, 2**31), chunk_id=st.integers(0, 2**31),
        retry_after_ms=st.integers(0, 2**32 - 1),
    ),
    st.builds(Cancel, job_id=st.integers(0, 2**31)),
)


@settings(max_examples=200)
@given(messages)
def test_protocol_roundtrip(msg):
    assert decode_msg(encode_msg(msg)) == msg


# ---------------------------------------------------------------------------
# binary fast-path codec (ISSUE 4): hot messages round-trip through the
# struct-packed encoding, binary and JSON peers agree on meaning, and
# corruption/truncation of a binary payload is a ProtocolError — never a
# mis-parse (tests/test_codec.py carries the deterministic golden-vector
# and exhaustive-corruption versions, since this image lacks hypothesis)
# ---------------------------------------------------------------------------

from tpuminter.protocol import ProtocolError, payload_is_binary  # noqa: E402

hot_messages = st.one_of(
    st.builds(
        Join,
        backend=st.sampled_from(
            ["cpu", "jax", "tpu", "pod", "native", "instant", ""]
        ),
        lanes=st.integers(1, 2**32 - 1),
        span=st.integers(0, 2**64 - 1),
        codec=st.sampled_from(["json", "bin"]),
    ),
    st.builds(
        Result,
        job_id=st.integers(0, 2**64 - 1),
        mode=st.sampled_from([PowMode.MIN, PowMode.TARGET, PowMode.SCRYPT]),
        nonce=st.integers(0, 2**64 - 1),
        hash_value=st.integers(0, 2**256 - 1),
        found=st.booleans(),
        searched=st.integers(0, 2**64 - 1),
        chunk_id=st.integers(0, 2**64 - 1),
    ),
    st.builds(
        Assign,
        job_id=st.integers(0, 2**64 - 1),
        chunk_id=st.integers(0, 2**64 - 1),
        lower=st.integers(0, 2**32 - 1),
        upper=st.integers(0, 2**64 - 1),
    ),
    st.builds(
        Refuse,
        job_id=st.integers(0, 2**64 - 1),
        chunk_id=st.integers(0, 2**64 - 1),
        retry_after_ms=st.integers(0, 2**32 - 1),
    ),
    st.builds(Cancel, job_id=st.integers(0, 2**64 - 1)),
)


@settings(max_examples=200)
@given(hot_messages)
def test_binary_codec_roundtrip_and_cross_codec_agreement(msg):
    wire = encode_msg(msg, binary=True)
    assert payload_is_binary(wire)
    assert decode_msg(wire) == msg
    assert decode_msg(memoryview(wire)) == msg  # the zero-copy path
    # a JSON peer describing the same message decodes identically
    assert decode_msg(encode_msg(msg)) == msg


@settings(max_examples=200)
@given(hot_messages, st.data())
def test_binary_codec_corruption_raises_never_misparses(msg, data):
    wire = bytearray(encode_msg(msg, binary=True))
    i = data.draw(st.integers(0, len(wire) - 1))
    wire[i] ^= data.draw(st.integers(1, 255))
    with pytest.raises(ProtocolError):
        decode_msg(bytes(wire))


@settings(max_examples=200)
@given(hot_messages, st.data())
def test_binary_codec_truncation_raises_never_misparses(msg, data):
    wire = encode_msg(msg, binary=True)
    keep = data.draw(st.integers(0, len(wire) - 1))
    with pytest.raises(ProtocolError):
        decode_msg(wire[:keep])


# ---------------------------------------------------------------------------
# journal record stream (tpuminter.journal): the bundled-codec
# corruption contract applied to disk, plus replay idempotency
# ---------------------------------------------------------------------------

from tpuminter.journal import encode_record, replay, scan  # noqa: E402
from tpuminter.protocol import request_to_obj  # noqa: E402

_journal_records = st.lists(
    st.one_of(
        st.builds(lambda e: {"k": "boot", "epoch": e}, st.integers(1, 50)),
        st.builds(
            lambda i, req: {"k": "job", "id": i, "req": request_to_obj(req)},
            st.integers(1, 6), min_requests,
        ),
        st.builds(
            lambda i, lo, size, h, s: {
                "k": "settle", "id": i, "lo": lo, "hi": lo + size,
                "h": f"{h:x}", "n": lo, "s": s,
            },
            st.integers(1, 6), st.integers(0, 900), st.integers(0, 200),
            st.integers(0, 2**64 - 1), st.integers(1, 500),
        ),
        st.builds(
            lambda i: {
                "k": "finish", "id": i, "ckey": "c", "cjid": i,
                "mode": "min", "n": 1, "h": "aa", "found": True, "s": 9,
            },
            st.integers(1, 6),
        ),
        st.builds(lambda i: {"k": "abandon", "id": i}, st.integers(1, 6)),
    ),
    min_size=1,
    max_size=12,
)


@settings(max_examples=80)
@given(_journal_records, st.data())
def test_journal_corruption_yields_only_a_clean_prefix(records, data):
    """Mirror of the bundled-codec property: a 1-byte flip anywhere in
    the journal may unframe everything after it, but what DOES decode
    is an exact prefix of the original records — corruption can only
    look like loss of a suffix, never like different records."""
    blob = bytearray(b"".join(encode_record(r) for r in records))
    i = data.draw(st.integers(0, len(blob) - 1))
    blob[i] ^= data.draw(st.integers(1, 255))
    got, _ = scan(bytes(blob))
    assert len(got) < len(records)
    assert got == records[: len(got)]


@settings(max_examples=80)
@given(_journal_records, st.data())
def test_journal_truncation_yields_only_a_clean_prefix(records, data):
    blob = b"".join(encode_record(r) for r in records)
    keep = data.draw(st.integers(0, len(blob) - 1))
    got, clean = scan(blob[:keep])
    assert len(got) < len(records)
    assert got == records[: len(got)]
    assert clean <= keep


def _state_key(state):
    return (
        state.boot_epoch, state.next_job_id,
        {j: (tuple(job.remaining), job.best, job.hashes_done)
         for j, job in state.jobs.items()},
        dict(state.winners),
    )


def _coordinator_order(records):
    """The order a coordinator writes: a job's settle/finish/abandon
    records follow its one job record. Replaying an orphan record twice
    is not a journal any coordinator produces (the second copy would
    find the job the first copy lacked)."""
    seen, out = set(), []
    for rec in records:
        if rec["k"] == "job":
            if rec["id"] in seen:
                continue
            seen.add(rec["id"])
        elif "id" in rec and rec["id"] not in seen:
            continue
        out.append(rec)
    return out


@settings(max_examples=60, deadline=None)
@given(_journal_records)
def test_journal_double_replay_idempotent(records):
    records = _coordinator_order(records)
    assert _state_key(replay(records)) == _state_key(
        replay(records + records)
    )


@settings(max_examples=60, deadline=None)
@given(
    nonce_bits=st.integers(2, 10),
    segs=st.integers(1, 8),
    data=st.data(),
)
def test_beacon_partial_settles_subtract_exactly(nonce_bits, segs, data):
    """Beacon recovery (ISSUE 14): sub-chunk progress beacons journal as
    ordinary settle records over a PREFIX of an in-flight chunk — zero
    journal-format change — so replaying any mix of beacon prefixes and
    whole-chunk settles must leave exactly the set-model's un-settled
    indices remaining, with ``hashes_done`` matching the covered count.
    (tests/test_roll_budget.py carries a deterministic seeded mirror,
    since this image lacks hypothesis.)"""
    from tpuminter.journal import merge_ranges
    from tpuminter.protocol import PowMode as _PM, Request as _Req

    total = segs << nonce_bits
    req = _Req(
        job_id=1, mode=_PM.TARGET, lower=0, upper=total - 1,
        header=_GENESIS80, target=1, coinbase_prefix=b"p",
        coinbase_suffix=b"s", extranonce_size=4, nonce_bits=nonce_bits,
    )
    records = [{"k": "job", "id": 1, "req": request_to_obj(req)}]
    covered = set()
    cuts = sorted(data.draw(st.sets(st.integers(1, total - 1), max_size=4)))
    for lo, hi in zip([0] + cuts, [c - 1 for c in cuts] + [total - 1]):
        for _ in range(data.draw(st.integers(0, 2))):
            if lo > hi - 1:
                break
            hw = data.draw(st.integers(lo, hi - 1))
            records.append({
                "k": "settle", "id": 1, "lo": lo, "hi": hw,
                "n": lo, "s": hw - lo + 1, "h": "ff",
            })
            covered.update(range(lo, hw + 1))
            lo = hw + 1  # the live chunk advances past the beacon
        if data.draw(st.booleans()) and lo <= hi:
            records.append({
                "k": "settle", "id": 1, "lo": lo, "hi": hi,
                "n": lo, "s": hi - lo + 1, "h": "ff",
            })
            covered.update(range(lo, hi + 1))
    state = replay(records)
    want, g = [], 0
    while g < total:
        if g in covered:
            g += 1
            continue
        start = g
        while g < total and g not in covered:
            g += 1
        want.append((start, g - 1))
    assert merge_ranges(state.jobs[1].remaining) == want
    assert state.jobs[1].hashes_done == len(covered)


# ---------------------------------------------------------------------------
# WAL shipping stream (tpuminter.replication): the journal corruption
# contract over the wire, plus standby ingestion invariants
# (deterministic mirrors live in tests/test_replication.py — this image
# lacks hypothesis)
# ---------------------------------------------------------------------------

from tpuminter.journal import RecoveredState, scan_with_cursor  # noqa: E402
from tpuminter.protocol import WalBatch  # noqa: E402


@settings(max_examples=80)
@given(_journal_records, st.data())
def test_shipped_batch_corruption_applies_only_an_exact_prefix(
    records, data
):
    """The standby scans every shipped batch before touching its
    shadow: a 1-byte flip anywhere in the batch must yield an exact
    record prefix (corruption on the link can only look like loss of a
    suffix — the resumed stream re-ships the rest)."""
    blob = bytearray(b"".join(encode_record(r) for r in records))
    i = data.draw(st.integers(0, len(blob) - 1))
    blob[i] ^= data.draw(st.integers(1, 255))
    got, clean, _last = scan_with_cursor(bytes(blob))
    assert got == records[: len(got)]
    assert clean <= len(blob)


@settings(max_examples=60, deadline=None)
@given(_journal_records, st.data())
def test_incremental_shadow_apply_equals_full_replay(records, data):
    """Standby ingestion applies records batch-by-batch as they ship;
    wherever the batch boundaries fall, the shadow must equal replaying
    the stream at once — so a cursor-resumed standby that replays no
    record twice converges on the same state (and min-folds keep the
    double-apply case idempotent regardless)."""
    shadow = RecoveredState()
    i = 0
    while i < len(records):
        step = data.draw(st.integers(1, 4))
        for rec in records[i : i + step]:
            shadow.apply(rec)
        i += step
    assert _state_key(shadow) == _state_key(replay(records))


@settings(max_examples=60)
@given(
    st.integers(0, 2**64 - 1), st.binary(max_size=600), st.data()
)
def test_walbatch_envelope_corruption_raises_never_misparses(
    offset, payload, data
):
    """The shipping envelope itself (binary tag 0xB8) is under the same
    corruption contract as every other binary message: any single-byte
    flip raises ProtocolError, never a different batch."""
    wire = bytearray(encode_msg(WalBatch(offset, payload), binary=True))
    i = data.draw(st.integers(0, len(wire) - 1))
    wire[i] ^= data.draw(st.integers(1, 255))
    with pytest.raises(ProtocolError):
        decode_msg(bytes(wire))


# ---------------------------------------------------------------------------
# codec-conformance checker (ISSUE 9): the static analyzer's table core
# must flag random kind tables iff they violate the PR 4 invariants
# ---------------------------------------------------------------------------

from tpuminter.analysis.codec_conformance import (  # noqa: E402
    JSON_SNIFF_BYTE,
    check_table,
    struct_size,
)

_fmt_field = st.sampled_from(list("BHIQ"))


@st.composite
def _kind_tables(draw):
    n = draw(st.integers(1, 8))
    kinds = []
    for i in range(n):
        body = "".join(draw(st.lists(_fmt_field, min_size=1, max_size=5)))
        kinds.append({
            "name": f"_K{i}",
            "module": draw(st.sampled_from(["a.py", "b.py"])),
            "line": i + 1,  # unique: the length-collision tiebreak
            "tag": draw(st.one_of(st.none(), st.integers(0, 255))),
            "fmt": "<" + body,
            "variable": draw(st.booleans()),
            "has_crc": draw(st.booleans()),
        })
    return kinds


def _expected_violations(kinds):
    """Independent oracle for check_table: the set of
    ``(violation, kind_name)`` pairs the invariants demand."""
    expected = set()
    by_tag = {}
    for k in kinds:
        if k["tag"] is not None:
            by_tag.setdefault(k["tag"], []).append(k)
    for tag, group in by_tag.items():
        for k in group[1:]:
            expected.add(("duplicate-tag", k["name"]))
        if tag == JSON_SNIFF_BYTE:
            for k in group:
                expected.add(("json-collision", k["name"]))
    by_mod = {}
    for k in kinds:
        if k["fmt"] and not k["variable"]:
            by_mod.setdefault(k["module"], []).append(k)
    for group in by_mod.values():
        by_size = {}
        for k in group:
            size = struct_size(k["fmt"])
            if size is not None:
                by_size.setdefault(size, []).append(k)
        for clash in by_size.values():
            for k in sorted(clash, key=lambda k: k["line"])[1:]:
                expected.add(("length-collision", k["name"]))
    for k in kinds:
        body = k["fmt"][1:]
        if k["tag"] is not None and not body.startswith("B"):
            expected.add(("tag-not-first", k["name"]))
        if not k["has_crc"]:
            expected.add(("missing-crc", k["name"]))
    return expected


@settings(max_examples=200)
@given(_kind_tables())
def test_codec_checker_flags_iff_invariant_violated(kinds):
    """Soundness AND completeness of the table core: a random kind
    table is flagged exactly where the distinct-length / CRC / tag
    invariants are broken — no false alarms, no misses."""
    got = {(v["violation"], v["kind"]) for v in check_table(kinds)}
    assert got == _expected_violations(kinds)


@settings(max_examples=60)
@given(_kind_tables())
def test_codec_checker_clean_table_stays_clean(kinds):
    """Repairing every violation yields a table the checker accepts:
    distinct tags, distinct lengths, CRC everywhere, tag byte first."""
    for i, k in enumerate(kinds):
        k["tag"] = 0xA0 + i              # distinct, never 0x7B
        k["fmt"] = "<B" + "B" * i        # distinct sizes, tag first
        k["variable"] = False
        k["has_crc"] = True
    assert check_table(kinds) == []


# ---------------------------------------------------------------------------
# jittered_backoff (lsp.params): the redial-delay contract every
# reconnect loop leans on under a long partition (deterministic mirrors
# live in tests/test_chaos.py — this image lacks hypothesis)
# ---------------------------------------------------------------------------

from tpuminter.lsp.params import jittered_backoff  # noqa: E402


@settings(max_examples=120)
@given(
    base=st.floats(0.001, 2.0),
    factor=st.floats(1.0, 64.0),
    seed=st.integers(0, 2**32),
    n=st.integers(1, 64),
)
def test_backoff_every_draw_within_jittered_envelope(base, factor, seed, n):
    """Each draw is the doubling envelope value ``min(base·2^k, cap)``
    under a uniform [0.5, 1.5) jitter — so no wait ever exceeds
    ``cap · 1.5``, the ceiling bounding every redial loop's patience,
    and no wait collapses below half the envelope (lockstep-free but
    never a hot spin)."""
    cap = base * factor
    gen = jittered_backoff(base, cap, random.Random(seed))
    envelope = base
    for _ in range(n):
        got = next(gen)
        assert envelope * 0.5 <= got <= envelope * 1.5
        assert got <= cap * 1.5
        # the unjittered envelope is monotone and capped — the next
        # draw's bounds can only move up, never past the cap
        envelope = min(envelope * 2, cap)
        assert envelope <= cap


@settings(max_examples=80)
@given(
    base=st.floats(0.001, 2.0),
    factor=st.floats(1.0, 64.0),
    seed=st.integers(0, 2**32),
)
def test_backoff_saturates_at_cap_and_is_seed_deterministic(
    base, factor, seed
):
    """After ``ceil(log2(cap/base))`` doublings every draw comes from
    the capped regime ``[cap/2, cap·1.5]`` — a partition that outlives
    the ramp gets a steady bounded redial cadence, not unbounded growth
    — and the whole sequence replays from the rng seed."""
    import math

    cap = base * factor
    ramp = max(0, math.ceil(math.log2(max(factor, 1.0)))) + 1
    gen = jittered_backoff(base, cap, random.Random(seed))
    for _ in range(ramp):
        next(gen)
    tail = [next(gen) for _ in range(20)]
    assert all(cap * 0.5 <= d <= cap * 1.5 for d in tail)
    gen_a = jittered_backoff(base, cap, random.Random(seed))
    gen_b = jittered_backoff(base, cap, random.Random(seed))
    assert [next(gen_a) for _ in range(30)] == [
        next(gen_b) for _ in range(30)
    ]


# ---------------------------------------------------------------------------
# winner/dedup-table bound (ISSUE 13): the eviction policy may shrink
# the table, never break exactly-once (deterministic seeded mirror
# lives in tests/test_control_plane.py — this image lacks hypothesis)
# ---------------------------------------------------------------------------

import time as _time  # noqa: E402
from collections import OrderedDict  # noqa: E402

from tpuminter.coordinator import Coordinator, _Winner  # noqa: E402

from tests.test_control_plane import _trim_oracle  # noqa: E402

_dummy_result = Result(
    1, PowMode.MIN, nonce=1, hash_value=1, found=True, searched=1,
    chunk_id=0,
)

_winner_entries = st.lists(
    st.tuples(
        st.booleans(),                 # durable (finish record fsynced)
        st.booleans(),                 # has parked re-submitters
        st.booleans(),                 # older than any ttl
    ),
    max_size=24,
)


@settings(max_examples=200)
@given(
    _winner_entries,
    st.integers(0, 16),                # winners_cap
    st.sampled_from([0.0, 100.0]),     # winners_ttl (0 = size-only)
)
def test_winner_trim_never_evicts_unacked(entries, cap, ttl):
    """Whatever the size/age pressure, ``_trim_winners`` removes
    exactly the oracle's evictable set and never an un-acknowledged
    entry (not durable yet, or with waiters parked on the durability
    callback) — the bound may be exceeded, exactly-once may not."""
    now = _time.time()
    table = OrderedDict()
    for i, (durable, waiter, stale) in enumerate(entries):
        table[("ck%d" % i, i)] = _Winner(
            _dummy_result, durable=durable,
            waiters=[7] if waiter else [],
            ts=now - (1000.0 if stale else 0.0),
        )
    unacked = {k for k, w in table.items() if not w.durable or w.waiters}
    expected = _trim_oracle(table, cap, ttl, now)

    coord = Coordinator.__new__(Coordinator)
    coord._winners = OrderedDict(table)
    coord._winners_cap = cap
    coord._winners_ttl = ttl
    coord._wall = lambda: now
    coord.stats = {"winners_evicted": 0}
    coord._trim_winners()

    survivors = set(coord._winners)
    assert unacked <= survivors
    assert set(table) - survivors == expected
    assert coord.stats["winners_evicted"] == len(expected)


# ---------------------------------------------------------------------------
# fold disciplines (ISSUE 15): the coverage-gated fold state must make
# every discipline — the non-idempotent sum included — exactly-once
# under arbitrary chunk partitions, delivery orders, duplicate
# deliveries, and beacon-style prefix splits (deterministic seeded
# mirrors live in tests/test_workloads.py — this image lacks
# hypothesis)
# ---------------------------------------------------------------------------

from tpuminter.workloads import (  # noqa: E402
    FMin,
    FSum,
    FirstMatch,
    TopK,
    absorb,
    new_state,
)
from tpuminter.workloads import hashcore as _hc  # noqa: E402

_FOLD_MAKERS = (
    lambda: FMin(),
    lambda: TopK(3),
    lambda: FirstMatch(1 << 60),
    lambda: FSum(),
)


def _fold_vals(seed, lo, hi):
    return [_hc.objective(seed, i) for i in range(lo, hi + 1)]


@st.composite
def _chunk_schedules(draw):
    """A partition of [0, hi] into chunks, a shuffled delivery order,
    and a set of duplicate deliveries injected at arbitrary points."""
    hi = draw(st.integers(5, 200))
    n_cuts = draw(st.integers(0, 8))
    cuts = sorted(draw(st.sets(st.integers(1, hi), max_size=n_cuts)))
    spans, at = [], 0
    for c in list(cuts) + [hi + 1]:
        spans.append((at, c - 1))
        at = c
    order = draw(st.permutations(list(range(len(spans)))))
    dups = draw(st.lists(
        st.integers(0, len(spans) - 1), max_size=3,
    ))
    return spans, list(order) + dups


@settings(max_examples=80, deadline=None)
@given(
    fold_i=st.integers(0, len(_FOLD_MAKERS) - 1),
    seed=st.integers(0, 2**32 - 1),
    sched=_chunk_schedules(),
)
def test_fold_state_is_schedule_independent(fold_i, seed, sched):
    """Any delivery order with any duplicates lands on the in-order,
    exactly-once state: absorb's coverage gate + the folds' assoc/comm
    combine are jointly what lets replay, out-of-order settles, and WAL
    merges share one mechanism."""
    fold = _FOLD_MAKERS[fold_i]()
    spans, order = sched
    settles = [
        (a, b, fold.of_batch(a, _fold_vals(seed, a, b))) for a, b in spans
    ]
    baseline = new_state(fold)
    for a, b, acc in settles:
        assert absorb(fold, baseline, a, b, acc)
    state = new_state(fold)
    for i in order:
        a, b, acc = settles[i]
        absorb(fold, state, a, b, acc)   # duplicates must bounce
    assert state == baseline


@settings(max_examples=80, deadline=None)
@given(
    fold_i=st.integers(0, len(_FOLD_MAKERS) - 1),
    seed=st.integers(0, 2**32 - 1),
    hi=st.integers(1, 150),
    data=st.data(),
)
def test_fold_beacon_prefix_split_settles_exactly(fold_i, seed, hi, data):
    """A chunk settled as prefix-beacon + remainder equals the whole
    chunk at once, and replaying the beacon is a no-op — ISSUE 14's
    sub-chunk progress shape is safe on every discipline. First-match
    probes are schedule-relative under early-cancel, so only its
    decided (index, value) must agree."""
    fold = _FOLD_MAKERS[fold_i]()
    cut = data.draw(st.integers(0, hi - 1))
    whole = new_state(fold)
    assert absorb(fold, whole, 0, hi, fold.of_batch(0, _fold_vals(seed, 0, hi)))
    beacon = fold.of_batch(0, _fold_vals(seed, 0, cut))
    rest = fold.of_batch(cut + 1, _fold_vals(seed, cut + 1, hi))
    split = new_state(fold)
    assert absorb(fold, split, 0, cut, beacon)
    assert absorb(fold, split, cut + 1, hi, rest)
    assert not absorb(fold, split, 0, cut, beacon)
    assert split["covered"] == whole["covered"] == [[0, hi]]
    if isinstance(fold, FirstMatch):
        assert split["acc"][:2] == whole["acc"][:2]
    else:
        assert split["acc"] == whole["acc"]


@settings(max_examples=120)
@given(
    v=st.integers(0, 2**64 - 1),
    i=st.integers(0, 2**64 - 1),
    probes=st.integers(1, 2**64 - 1),
    total=st.integers(0, 2**128 - 1),
    count=st.integers(0, 2**64 - 1),
    pairs=st.lists(
        st.tuples(st.integers(0, 2**64 - 1), st.integers(0, 2**64 - 1)),
        max_size=8, unique_by=lambda p: p[1],
    ),
    data=st.data(),
)
def test_fold_payload_roundtrip_and_corruption(
    v, i, probes, total, count, pairs, data
):
    """Every discipline's chunk-partial frame round-trips any in-range
    accumulator, and any single-byte corruption is a loud ValueError —
    the CRC trailer is the ONLY corruption check these bytes get on the
    JSON fallback, so it must hold unconditionally."""
    cases = [
        (FMin(), [v, i]),
        (TopK(8), sorted([list(p) for p in pairs])),
        (FirstMatch(0), [i, v, probes]),
        (FSum(), [total, count]),
    ]
    for fold, acc in cases:
        wire = fold.encode(acc)
        assert fold.decode(wire) == acc
        pos = data.draw(st.integers(0, len(wire) - 1))
        flip = data.draw(st.integers(1, 255))
        bad = bytearray(wire)
        bad[pos] ^= flip
        with pytest.raises(ValueError):
            fold.decode(bytes(bad))


# ---------------------------------------------------------------------------
# device-lane hashcore engine (ISSUE 17): the u32-pair sweep must be
# bit-for-bit the host fold chain on ARBITRARY (seed, range, fold) —
# the hypothesis mirror of tests/test_hashcore_dev.py's seeded pins
# ---------------------------------------------------------------------------

from tpuminter.ops import splitmix as _sm  # noqa: E402


@settings(max_examples=100, deadline=None)
@given(seed=st.integers(0, 2**64 - 1), index=st.integers(0, 2**64 - 1))
def test_u32_pair_objective_matches_scalar(seed, index):
    """The hi/lo-word splitmix64 (carry adds, 16-bit-limb multiplies,
    cross-word shifts) agrees with the Python-int scalar at every
    (seed, index) hypothesis can throw at it — shrinking lands on the
    exact carry/shift boundary if one is off."""
    assert _sm.lane_objective(seed, [index]) == [
        _hc.objective(seed, index)
    ]


_DEV_MAKERS = (
    lambda thr, k: (FMin(), "fmin", 1),
    lambda thr, k: (TopK(k), "topk", k),
    lambda thr, k: (FirstMatch(thr), "fmatch", 1),
    lambda thr, k: (FSum(), "fsum", 1),
)


@settings(max_examples=25, deadline=None)
@given(
    fold_i=st.integers(0, len(_DEV_MAKERS) - 1),
    seed=st.integers(0, 2**64 - 1),
    lo=st.integers(0, 2**63),
    n=st.integers(1, 900),
    thr=st.integers(0, 2**64 - 1),
    k=st.integers(1, 8),
)
def test_device_sweep_equals_host_fold(fold_i, seed, lo, n, thr, k):
    """Window-granular device partials combined across ragged windows
    equal one host ``of_batch`` over the whole range, every discipline
    (first-match early-stops on device; its accumulator is granularity-
    independent by the probes construction). The shared (256, 2) shape
    means one compile per variant per process."""
    fold, variant, kk = _DEV_MAKERS[fold_i](thr, k)
    hi = lo + n - 1
    sweep = _sm.LaneSweep(variant, 256, 2, kk, "jnp")
    dev = fold.initial()
    g = lo
    while g <= hi:
        e = min(g + sweep.window - 1, hi)
        dev = fold.combine(
            dev, sweep.resolve(sweep.dispatch(seed, g, e, thr), g, e)
        )
        if fold.is_final(dev):
            break
        g = e + 1
    assert dev == fold.of_batch(lo, _fold_vals(seed, lo, hi))


@settings(max_examples=100)
@given(
    seed=st.integers(0, 2**32 - 1),
    lo=st.integers(0, 1000),
    span=st.integers(0, 40),
    k=st.integers(1, 8),
)
def test_topk_ties_always_rank_the_lowest_global_index(seed, lo, span, k):
    """However a range is chunked, top-k's answer is the first k pairs
    of the (value, index)-sorted scan — equal values resolve to the
    LOWER global index, one deterministic list per job."""
    hi = lo + span
    fold = TopK(k)
    values = _fold_vals(seed, lo, hi)
    want = sorted([val, lo + off] for off, val in enumerate(values))[:k]
    mid = lo + span // 2
    acc = fold.combine(
        fold.of_batch(lo, values[: mid - lo + 1]),
        fold.of_batch(mid + 1, values[mid - lo + 1:]),
    )
    assert acc == want


# ---------------------------------------------------------------------------
# shared-compression scheduling (ISSUE 16; the seeded mirrors sit with
# the layer's other pins)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**32 - 1),
    b=st.integers(1, 6),
    width=st.sampled_from([32, 64]),
    cand_bits=st.sampled_from([8, 32]),
)
def test_batched_sweep_matches_hashlib_scan(seed, b, width, cand_bits):
    """The shared-schedule sweep returns the ``[found, first_goff]``
    pair a hashlib scan of the same rolled headers gives, on any row
    set — random extranonces, bases and caps, ragged valid counts
    included."""
    import struct

    import numpy as np
    import jax.numpy as jnp

    from tpuminter import rolled
    from tpuminter.ops import merkle

    rng = np.random.RandomState(seed)
    prefix, suffix = rng.bytes(41), rng.bytes(60)
    hdr80 = chain.GENESIS_HEADER.pack()
    ens = rng.randint(0, 1 << 32, b, dtype=np.uint32)
    mids, tails = merkle.roll_batch_deduped(
        *merkle.roll_template(hdr80, prefix, suffix, 4, ()),
        np.zeros(b, np.uint32), ens)
    bases = rng.randint(0, 1 << 20, b).astype(np.uint32)
    valids = rng.randint(0, width + 1, b).astype(np.uint32)
    goffs = (np.arange(b, dtype=np.uint64) * width).astype(np.uint32)
    cap = int(rng.randint(0, 1 << 32))
    got = rolled._jnp_batched_candidate_sweep(
        mids, tails, jnp.asarray(bases), jnp.asarray(valids),
        jnp.asarray(goffs), jnp.uint32(cap), width, cand_bits,
    )

    cb = chain.CoinbaseTemplate(prefix, suffix, 4)
    first = 0xFFFFFFFF
    for en, base, valid, goff in zip(ens, bases, valids, goffs):
        p76 = chain.rolled_header(hdr80, cb, (), int(en)).pack()[:76]
        for c in range(int(valid)):
            h = chain.hash_to_int(
                chain.dsha256(p76 + struct.pack("<I", int(base) + c)))
            ok = (h >> 224 == 0 and (h >> 192) & 0xFFFFFFFF <= cap
                  if cand_bits == 32 else h >> (256 - cand_bits) == 0)
            if ok:
                first = min(first, int(goff) + c)
                break
    assert np.asarray(got).tolist() == [int(first != 0xFFFFFFFF), first]


@settings(max_examples=20, deadline=None)
@given(ens=st.lists(st.integers(0, 2**64 - 1), min_size=1, max_size=12))
def test_roll_batch_deduped_any_row_multiset(ens):
    """Dedup-then-gather ≡ rolling every row, for ANY multiset of
    64-bit extranonces (duplicates, all-equal, all-distinct)."""
    import numpy as np
    import jax.numpy as jnp

    from tpuminter.ops import merkle

    rng = np.random.RandomState(16)
    prefix, suffix = rng.bytes(41), rng.bytes(60)
    layout, words = merkle.roll_template(
        chain.GENESIS_HEADER.pack(), prefix, suffix, 8, ()
    )
    en = np.asarray(ens, dtype=np.uint64)
    en_hi = (en >> np.uint64(32)).astype(np.uint32)
    en_lo = (en & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    # every row rolled on its own, no dedup
    want_m, want_t = merkle.extranonce_roll(
        layout, *words, jnp.asarray(en_hi), jnp.asarray(en_lo),
        jnp.arange(len(ens), dtype=jnp.int32))
    got_m, got_t = merkle.roll_batch_deduped(layout, words, en_hi, en_lo)
    assert np.array_equal(np.asarray(want_m), np.asarray(got_m))
    assert np.array_equal(np.asarray(want_t), np.asarray(got_t))


@settings(max_examples=40)
@given(
    mid=st.lists(st.integers(0, 2**32 - 1), min_size=8, max_size=8),
    tail=st.lists(st.integers(0, 2**32 - 1), min_size=3, max_size=3),
    nonce=st.integers(0, 2**32 - 1),
)
def test_prepared_schedule_folds_like_unshared(mid, tail, nonce):
    """prepare_hdr + hash_prepared_e60_e61 ≡ hash_sym_e60_e61 over the
    all-int domain — both fully const-fold, and agree on every bit."""
    from tpuminter.ops import sha256 as ops
    from tpuminter.ops import symbolic as sym

    bswap = lambda x: int.from_bytes(x.to_bytes(4, "little"), "big")
    block = [*tail, bswap(nonce), *ops.HEADER_TAIL_PAD]
    want = sym.hash_sym_e60_e61(mid, [block], (), 0, 0)
    got = sym.hash_prepared_e60_e61(sym.prepare_hdr(mid, *tail), nonce)
    assert isinstance(got[0], int) and isinstance(got[1], int)
    assert got == want
