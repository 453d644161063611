"""Application protocol: the messages that travel between roles.

Capability-equivalent rebuild of the reference's ``bitcoin/message.go``
(SURVEY.md §2 #7; mount empty per §0): ``Join`` / ``Request`` / ``Result``
carried as LSP payloads. Like the reference we JSON-encode the app layer
(the frames below it are binary); unlike the reference, a ``Request``
speaks two proof-of-work dialects:

- ``PowMode.MIN`` — the reference's toy PoW: over ``[lower, upper]``
  (inclusive, as in the reference), find the nonce *minimizing*
  ``toy_hash(data, nonce)``.
- ``PowMode.TARGET`` — the real-Bitcoin capability delta demanded by
  BASELINE.json:6-12: find any nonce with
  ``double-SHA256(header ‖ nonce) <= target``.
- ``PowMode.SCRYPT`` — the memory-hard variant (BASELINE.json:11,
  Litecoin N=1024/r=1/p=1): same header/target shape as TARGET with
  ``chain.scrypt_hash`` as the PoW function.

Both dialects fold the same way: every chunk Result carries the *minimum*
hash over its range and the argmin nonce, which is an associative
reduction the coordinator (and, on device, ``jax.lax`` argmin trees) can
combine in any order. TARGET mode additionally sets ``found`` when the
minimum beats the target, which lets the coordinator early-exit the job
and ``Cancel`` the other in-flight chunks — the control-plane half of the
"whole pod stops on the first sub-target hash" story (BASELINE.json:5;
the on-device half is the ICI or-reduce in ``tpuminter.mesh``).

**Binary fast path (codec v1).** The fleet-64 profile put ~16% of the
control-plane cost in this module's JSON round trip (PERF.md §Round 7),
so the HOT messages — the ones that flow once per chunk or per
connection: Assign, Result, Refuse, Cancel, Join — also have a
struct-packed encoding behind the same :func:`encode_msg` /
:func:`decode_msg` seam:

``tag:u8 ‖ fields… ‖ crc32:u32`` (little-endian)

The first byte discriminates the codec: JSON payloads always start with
``{`` (0x7B), which is not a valid binary tag, so a decoder accepts both
without negotiation. Tags 0xB1–0xB5 ARE version 1 of the binary codec —
a future layout change allocates new tags rather than reinterpreting
these. The trailing CRC32 (over everything before it) keeps the app
codec under the same corruption contract as the LSP frames and the
journal: a corrupted or truncated binary payload raises
:class:`ProtocolError`, never mis-parses (every message kind also has a
distinct total length, so even a corrupted tag cannot alias another
kind). Request and Setup stay JSON-only — they are the long tail
(rolled-job templates with ragged coinbase/branch fields, sent once per
job or per (worker, job)) and the compat path.

**No flag day.** Codec choice is per-connection and negotiated in band:
a worker advertises capability in its (JSON-compatible) ``Join`` via
``codec="bin"`` — an old coordinator ignores the unknown key and keeps
speaking JSON — and a binary-capable coordinator answers such a worker
with binary Assigns; the worker switches its own Results to binary only
after it has SEEN a binary payload from the coordinator (proof the peer
decodes them). Either side being older than the other therefore
degrades to JSON automatically, which the interop e2e pins
(tests/test_e2e.py).

**Roll-budget dialect (ISSUE 14).** For rolled jobs the natural unit of
dispatch is the *extranonce*, not the global index: at production
``nonce_bits=32`` a classic Assign covers a few thousand of the 2^32
nonces under one extranonce, so control-plane messages per unit of work
are ~4·10⁹× what they need to be. :class:`RollAssign` fixes that — it
says "mine extranonces ``[extranonce0, extranonce0+count)``, full
``2^nonce_bits`` nonces each" in one 33-byte message, and because one
such chunk can represent hours of work, :class:`Beacon` lets the worker
periodically report its settled global-index high-water (plus its
running min-fold candidate) so the coordinator can journal partial
settles, see real straggler progress, and re-mine only the un-settled
sub-range after a crash. Negotiation mirrors codec v1 exactly: a worker
advertises the dialect in its Join (``roll=True`` → JSON key
``"roll": 1`` / binary flag bit 0x02 — both invisible to old decoders),
the coordinator only sends RollAssign to workers that advertised it,
and a worker only emits Beacons for chunks that ARRIVED as a RollAssign
(proof the coordinator speaks the dialect). Either side being old
degrades to classic global-index Assigns with no flag day.

**Federation dialect (ISSUE 18).** An aggregator node speaks this
protocol in both directions: worker upward (its ``Join`` carries
``agg=<name>``, the aggregator hello) and coordinator downward to its
local fleet. Three extensions ride the same no-flag-day rules:

- ``RollAssign.lease_epoch`` / ``Beacon.lease_epoch`` — the lease
  fencing credential. A chunk whose un-beaconed suffix is re-leased to
  a sibling (work-stealing) bumps its job's lease epoch; the loser's
  late Beacons carry the old epoch and are rejected at settle, never
  double-counted. Epochs travel as NEW binary tags (0xBC/0xBD — v1
  tags never change meaning) and an omitted-when-zero JSON key, and
  the coordinator only stamps a non-zero epoch toward peers that sent
  the aggregator hello, so old workers never see an unknown layout.
- :class:`Steal` — aggregator → coordinator: "my local fleet is idle;
  re-lease me the un-beaconed suffix of a slow sibling's assignment".
  JSON-only (rare by construction).

**Streaming-fold dialect (ISSUE 20).** A client that sets
``Request.stream`` asks to watch its answer converge: the coordinator
pushes :class:`Emit` messages — monotone partial fold results gated on
JOURNALED settles only — at a bounded cadence before the final Result.
Same no-flag-day rules: ``"strm"`` is an omitted-when-False JSON key an
old coordinator ignores (the job then simply produces no partials), and
Emit rides a NEW tag (0xBE) an old client never receives because it
never asked to stream.
"""

from __future__ import annotations

import json
import struct
import zlib
from dataclasses import dataclass
from enum import Enum
from typing import Optional, Tuple, Union

__all__ = [
    "PowMode",
    "Join",
    "Request",
    "Result",
    "WorkResult",
    "Cancel",
    "Setup",
    "Assign",
    "RollAssign",
    "Beacon",
    "Steal",
    "Emit",
    "Refuse",
    "RepHello",
    "SyncFrom",
    "WalStart",
    "WalBatch",
    "SyncAck",
    "Message",
    "encode_msg",
    "decode_msg",
    "payload_is_binary",
    "request_to_obj",
    "request_from_obj",
    "ProtocolError",
    "MIN_UNTRACKED",
    "codec_stats",
]

#: Sentinel ``hash_value`` in an exhausted TARGET Result from a worker
#: that does not track the running 256-bit minimum (the fast TPU path
#: skips it to hit ≥1 GH/s). Loses every min-fold against a real hash,
#: so mixed fleets degrade gracefully; a final Result carrying it means
#: "range exhausted, no winner, minimum untracked" — consumers must not
#: present it as a real hash (the client CLI already prints a plain
#: "Exhausted" line for found=False).
MIN_UNTRACKED = (1 << 256) - 1


class ProtocolError(ValueError):
    """A payload that is not a well-formed app message."""


class PowMode(str, Enum):
    MIN = "min"        # toy PoW: minimize uint64 fold (reference parity)
    TARGET = "target"  # real PoW: double-SHA256(header) <= target
    SCRYPT = "scrypt"  # memory-hard PoW: scrypt(header) <= target (BASELINE.json:11)

    @property
    def targeted(self) -> bool:
        """True for the header-mining dialects (header + target + u32
        nonce; ``found`` means the target was beaten). Only the hash
        function differs between them."""
        return self in (PowMode.TARGET, PowMode.SCRYPT)


@dataclass(frozen=True)
class Join:
    """Worker → coordinator: I am a miner, give me work.

    ``backend`` names the worker implementation ("cpu", "jax", "tpu",
    "native"); ``lanes`` is a relative-throughput hint the scheduler may
    use to size chunks (1 = one CPU core's worth). ``span`` is the
    worker's internal pipeline-stage size in nonces (0 = no pipelining):
    a device worker sweeps whole slabs/pod-spans per dispatch call with
    several in flight, so the coordinator sizes fast-dialect chunks to
    cover multiple spans — a single-span chunk drains the pipeline at
    every chunk boundary (measured 9% at a 2^30 span, PERF.md).

    ``codec`` advertises the wire codecs this worker can DECODE:
    ``"json"`` (the default — and all any pre-binary peer ever says) or
    ``"bin"`` for the struct-packed fast path (module docstring). It is
    an advertisement, not a demand: the coordinator still decodes both
    from everyone, and only starts ENCODING binary toward a worker that
    advertised it.

    ``roll`` advertises the roll-budget dialect (module docstring): this
    worker understands :class:`RollAssign` and can emit :class:`Beacon`
    progress for such chunks. Same contract as ``codec``: an
    advertisement an old coordinator never sees (the JSON key is omitted
    when False and old decoders ignore it; the binary flag bit is one an
    old decoder never tests), and the coordinator only dispatches
    RollAssigns to workers that set it.

    ``workloads`` advertises the pluggable workload names this worker's
    registry (:mod:`tpuminter.workloads`) can compute — the same
    no-flag-day contract again: a Join carrying any name encodes as
    JSON (the binary Join layout predates the field and v1 layouts
    never change meaning; one JSON Join per connection costs nothing),
    the key is omitted when empty so old decoders ignore it, and the
    coordinator only dispatches a workload job to workers that
    advertised its name.

    ``agg`` is the aggregator hello (ISSUE 18): a non-empty value names
    a federation aggregator fronting a local fleet — it behaves as a
    worker on this connection, but the coordinator additionally (a)
    stamps lease epochs into its RollAssigns (the hello doubles as the
    lease-epoch capability advertisement; plain workers always see the
    classic epoch-free layout), (b) accepts :class:`Steal` requests
    from it, and (c) accounts its dispatches as delegated leases.
    Same no-flag-day contract: the JSON key is omitted when empty
    (a Join carrying it encodes as JSON — the v1 binary Join layout
    predates the field) and an old coordinator ignores it, degrading
    the aggregator to a plain worker.
    """

    backend: str = "cpu"
    lanes: int = 1
    span: int = 0
    codec: str = "json"
    roll: bool = False
    workloads: Tuple[str, ...] = ()
    agg: str = ""


@dataclass(frozen=True)
class Request:
    """Coordinator → worker: mine this nonce range. Also client →
    coordinator, where ``[lower, upper]`` is the whole job's range.

    MIN mode uses ``data``; TARGET mode uses ``header`` (80 bytes, nonce
    field ignored) + ``target`` (256-bit integer). ``upper`` is inclusive
    and bounded by the dialect's nonce width (2^32-1 for TARGET — the
    header nonce field is u32; 2^64-1 for MIN) so no range a worker
    accepts can overflow its hot loop. ``chunk_id`` identifies this
    specific dispatch; workers echo it in their Result so the scheduler
    can tell a live chunk's answer from a stale one (see coordinator).

    **Rolled (extranonce) jobs** (BASELINE.json:9-10): when
    ``coinbase_prefix is not None`` a TARGET job's search space is the
    (extranonce × nonce) product. ``[lower, upper]`` then ranges over
    *global indices* ``extranonce << nonce_bits | nonce``
    (``chain.split_global``); the header's merkle-root field is ignored
    and recomputed per extranonce from the coinbase split around its
    ``extranonce_size`` little-endian extranonce bytes, folded up
    ``branch``. ``nonce_bits`` is 32 in production; tests shrink it so a
    roll happens within a tractable sweep. Workers perform the roll on
    device (``ops.merkle.make_extranonce_roll_batch``).

    ``client_key`` is a durable client identity (any opaque string the
    client chooses once and reuses across reconnects). Connection ids
    are ephemeral — a coordinator restart or a client redial mints new
    ones — so exactly-once answers across either failure need a key
    that survives both: a re-submitted ``(client_key, job_id)`` is
    deduplicated against the journaled winners table or re-bound to the
    still-running job instead of spawning a duplicate (see
    ``tpuminter.journal``). Empty (the default) opts out: anonymous
    jobs keep the reference's connection-scoped lifetime.

    ``workload`` names a pluggable workload (:mod:`tpuminter.workloads`,
    ISSUE 15): empty means classic mining; otherwise ``data`` carries
    that workload's own tagged+CRC'd params frame, ``mode`` stays MIN
    (the u64-range dialect — workload indices are plain u64s), and the
    coordinator resolves the fold discipline, verifier, and compute
    seam from the registry. Workload chunk answers travel as
    :class:`WorkResult`, not :class:`Result`.

    ``stream`` opts this job into partial-result emission (ISSUE 20):
    the coordinator pushes :class:`Emit` snapshots of the running fold
    as journaled settles accumulate, before the final answer. Advisory
    — an old coordinator ignores the omitted-when-False JSON key and
    the client just sees the final Result; only workload jobs (those
    with a fold discipline) ever emit.
    """

    job_id: int
    mode: PowMode
    lower: int
    upper: int
    data: bytes = b""
    header: Optional[bytes] = None
    target: Optional[int] = None
    chunk_id: int = 0
    coinbase_prefix: Optional[bytes] = None
    coinbase_suffix: bytes = b""
    extranonce_size: int = 4
    branch: Tuple[bytes, ...] = ()
    nonce_bits: int = 32
    client_key: str = ""
    workload: str = ""
    stream: bool = False

    @property
    def rolled(self) -> bool:
        """True when this is an extranonce-rolling job."""
        return self.coinbase_prefix is not None

    def __post_init__(self) -> None:
        if self.rolled:
            if not self.mode.targeted:
                raise ProtocolError("extranonce rolling requires a targeted mode")
            if not 1 <= self.extranonce_size <= 8:
                raise ProtocolError("extranonce_size must be in [1, 8]")
            if not 1 <= self.nonce_bits <= 32:
                raise ProtocolError("nonce_bits must be in [1, 32]")
            for sib in self.branch:
                if len(sib) != 32:
                    raise ProtocolError("merkle branch entries must be 32 bytes")
            span_bits = min(64, self.nonce_bits + 8 * self.extranonce_size)
            limit = (1 << span_bits) - 1
        else:
            limit = 0xFFFFFFFF if self.mode.targeted else 0xFFFFFFFFFFFFFFFF
        if self.lower < 0 or self.upper < self.lower or self.upper > limit:
            raise ProtocolError(f"bad nonce range [{self.lower}, {self.upper}]")
        if self.mode.targeted:
            if self.header is None or len(self.header) != 80:
                raise ProtocolError("targeted modes need an 80-byte header")
            if self.target is None or self.target <= 0:
                raise ProtocolError("targeted modes need a positive target")


@dataclass(frozen=True)
class Result:
    """Worker → coordinator (per chunk) and coordinator → client (final).

    ``hash_value`` is the minimum hash over the searched range — a uint64
    for MIN mode, the uint256 little-endian integer of the double-SHA
    digest for TARGET mode — and ``nonce`` its argmin. ``found`` is True
    in MIN mode always, in TARGET mode iff ``hash_value <= target``.
    Workers that don't track the exhausted-range minimum (the fast TPU
    path) report :data:`MIN_UNTRACKED` instead of a real minimum.
    ``searched`` is the number of nonces actually examined (less than the
    range size when a TARGET hit early-exits a chunk); the coordinator's
    final Result to the client carries the job-wide total. ``chunk_id``
    echoes the Request being answered.
    """

    job_id: int
    mode: PowMode
    nonce: int
    hash_value: int
    found: bool = True
    searched: int = 0
    chunk_id: int = 0


@dataclass(frozen=True)
class WorkResult:
    """Worker → coordinator (per chunk) and coordinator → client
    (final) for pluggable workloads (:mod:`tpuminter.workloads`).

    The mining :class:`Result` hard-codes min-fold fields (nonce +
    hash); a workload answer is whatever its fold discipline says, so
    ``payload`` carries the discipline's own tagged + CRC-trailed
    chunk-partial frame, opaque to this layer — the payload CRC is
    load-bearing on the JSON fallback, where the hex field has no other
    corruption check. ``wid`` is the registered numeric workload id
    (cross-checked against the job's workload before verification);
    ``searched`` counts evaluated indices (first-match early-exit makes
    it smaller than the range), feeding the same accounting as mining's
    ``searched``. The found/empty distinction lives INSIDE the payload:
    each fold encodes its own "nothing here" shape, so this envelope
    never changes when a new discipline registers.
    """

    job_id: int
    chunk_id: int
    wid: int
    searched: int
    payload: bytes = b""


@dataclass(frozen=True)
class Setup:
    """Coordinator → worker: cache this job's template.

    Sent once per (worker, job) before the first :class:`Assign`, so the
    per-dispatch message stays tiny no matter how large the job payload
    is (a mainnet rolled job's coinbase + 12-deep branch is ~1.5 kB —
    re-shipping it on every chunk dispatch would dominate control-plane
    bytes). ``request`` is the client's full-range Request re-stamped
    with the coordinator's internal job id; its ``lower``/``upper`` are
    the whole job's range and are superseded per chunk by Assign.
    """

    request: Request


@dataclass(frozen=True)
class Assign:
    """Coordinator → worker: mine ``[lower, upper]`` of the job whose
    template a prior :class:`Setup` delivered. LSP's in-order delivery
    guarantees the Setup precedes every Assign referencing it."""

    job_id: int
    chunk_id: int
    lower: int
    upper: int


@dataclass(frozen=True)
class RollAssign:
    """Coordinator → worker: mine extranonces ``[extranonce0,
    extranonce0 + count)`` of the rolled job whose template a prior
    :class:`Setup` delivered — every one of them over the FULL
    ``2^nonce_bits`` header-nonce sweep. Equivalent to an
    :class:`Assign` of the global-index range ``[extranonce0 <<
    nonce_bits, (extranonce0 + count) << nonce_bits - 1]`` (the worker
    expands it exactly so, against the cached template's ``nonce_bits``),
    but one 33-byte message now covers ``count · 2^nonce_bits`` indices
    instead of a few thousand. Only sent to workers that advertised
    ``Join.roll`` (module docstring); progress inside the chunk flows
    back via :class:`Beacon`.

    ``lease_epoch`` is the federation fencing credential (ISSUE 18):
    the job's lease epoch at dispatch time. It is only ever non-zero
    toward peers that sent the aggregator hello (``Join.agg``) — a
    sibling steal bumps the epoch, so the victim's late progress
    claims carry a stale epoch and are fenced at settle."""

    job_id: int
    chunk_id: int
    extranonce0: int
    count: int
    lease_epoch: int = 0


@dataclass(frozen=True)
class Beacon:
    """Worker → coordinator: sub-chunk progress on a roll-budget chunk.

    ``high_water`` is the settled global-index high-water: every index
    of the chunk up to and including it has been verifiably swept with
    no winner found. ``nonce``/``hash_value`` carry the worker's running
    min-fold over the searched prefix (same semantics as a Result's
    argmin fields; :data:`MIN_UNTRACKED` when the fast path doesn't
    track it), so the coordinator's min bookkeeping stays exact even if
    the chunk later dies. The coordinator verifies the claimed pair like
    a Result, journals ``[chunk_lower, high_water]`` as a PARTIAL settle
    (ordinary settle record — interval subtraction in recovery already
    handles sub-ranges), and advances the in-flight chunk's lower bound,
    so crash recovery re-mines only the un-settled sub-range and
    hedging/eviction sees real straggler progress instead of a silent
    multi-hour chunk. Purely advisory: losing every Beacon degrades to
    pre-beacon behavior, and the final Result still settles the whole
    remainder.

    ``lease_epoch`` echoes the RollAssign's lease epoch (ISSUE 18):
    the coordinator rejects a Beacon whose epoch no longer matches the
    chunk's recorded lease — the loser of a sibling steal reports
    progress on a lease it no longer holds, and accepting it would
    double-count the stolen suffix."""

    job_id: int
    chunk_id: int
    high_water: int
    nonce: int
    hash_value: int
    lease_epoch: int = 0


@dataclass(frozen=True)
class Steal:
    """Aggregator → coordinator: my local fleet has idle capacity and
    nothing queued — re-lease me the un-beaconed suffix of a slow
    sibling's assignment (ISSUE 18 work-stealing).

    Purely a hint: the coordinator picks the victim (the oldest
    no-progress rolled chunk with at least one whole un-beaconed
    segment left, older than its ``steal_after`` threshold) or ignores
    the request. A successful steal bumps the job's lease epoch before
    re-dispatching the suffix, so the victim's late Beacons/Results
    are fenced, not double-counted. ``job_id`` restricts the hunt to
    one job (0 = any). JSON-only: steals are rare by construction
    (one per idle episode, rate-limited sender-side)."""

    job_id: int = 0


@dataclass(frozen=True)
class Emit:
    """Coordinator → client: a monotone partial result for a streaming
    workload job (ISSUE 20). Pushed before the final Result when the
    client's Request set ``stream``; never replaces it — the final
    Result/WorkResult still arrives and is the authoritative answer.

    ``payload`` is the job's fold discipline encoding of the running
    accumulator over the JOURNALED settled coverage only — un-durable
    state is never emitted, so partials can never regress across a
    coordinator kill -9 + journal replay (replay can only re-reach or
    extend what was already settled durably). ``covered`` / ``total``
    are settled-index count vs the job's whole domain span (the
    coverage fraction a client renders), ``seq`` is a per-job emission
    counter (strictly increasing; clients drop stale/duplicate seqs on
    redelivery). ``job_id`` is the CLIENT's job id, like a final
    Result. Purely advisory: losing every Emit degrades to the classic
    wait-for-exhaustion behavior."""

    job_id: int
    seq: int
    covered: int
    total: int
    payload: bytes = b""


@dataclass(frozen=True)
class Refuse:
    """Worker → coordinator: I cannot mine this dispatch (no cached
    template for its job). The recovery seam that keeps the template
    split self-healing: the coordinator requeues the chunk, forgets it
    ever Setup this worker for the job, and the next dispatch re-ships
    the template. Without it, any cache/`setup_sent` divergence (however
    caused) would wedge the worker busy-forever on a silently-dropped
    Assign.

    Coordinator → client (``retry_after_ms > 0``): admission control's
    explicit backpressure — the submission was refused (over-quota or
    over-capacity), come back after roughly ``retry_after_ms``
    milliseconds with jitter. Echoes the CLIENT's job_id (chunk_id 0).
    Clients honor it with jittered backoff and a re-submit; it never
    counts toward any eviction threshold (an admission Refuse is the
    coordinator doing its job, not a peer misbehaving)."""

    job_id: int
    chunk_id: int
    #: 0 = the classic worker-side template refusal; > 0 = an admission
    #: refusal carrying the coordinator's suggested retry delay
    retry_after_ms: int = 0


@dataclass(frozen=True)
class Cancel:
    """Coordinator → worker: stop mining ``job_id``, its answer is in.

    No reference analogue (the reference lets stale chunks run to
    completion and drops their results); a framework-grade scheduler wants
    the early-exit to propagate so device time isn't burned on dead work.
    Workers treat it as advisory — a late Result is still ignored server
    side.
    """

    job_id: int


@dataclass(frozen=True)
class RepHello:
    """Primary → standby, first message on a WAL-shipping connection:
    "I am (or claim to be) the coordinator of boot epoch ``epoch``;
    tell me where to resume". The epoch is the FENCING credential
    (tpuminter.replication): a standby rejects a hello whose epoch is
    below the primary it already follows, and a *promoted* standby —
    whose own epoch jumped a fencing stride ahead — rejects the dead
    primary's entire restart lineage, so a zombie primary's shipping
    stream can never corrupt the new coordinator."""

    epoch: int


@dataclass(frozen=True)
class SyncFrom:
    """Standby → primary: the durable resume cursor, derived by
    scanning the standby's local WAL copy (``journal.scan_with_cursor``)
    — ``offset`` bytes are already applied, the last record starts at
    ``last_start`` and carries stored CRC ``crc``. The primary
    validates the cursor against its own file (``journal.cursor_valid``)
    and resumes there, or restarts the stream at 0 when the files have
    diverged (compaction, corruption)."""

    offset: int
    last_start: int = -1
    crc: int = 0


@dataclass(frozen=True)
class WalStart:
    """Primary → standby: the next :class:`WalBatch` begins at byte
    ``offset`` of the primary's journal. ``offset == 0`` with local
    state present means FULL RESYNC: the standby truncates its copy and
    resets its shadow (the stream re-delivers a boot + snapshot)."""

    offset: int


@dataclass(frozen=True)
class WalBatch:
    """Primary → standby: ``data`` is a raw slice of the primary's
    journal file starting at byte ``offset`` — the already-framed
    length-prefixed+CRC records exactly as the flusher group-committed
    them (no re-encoding; shipping piggybacks on the WAL's own batch
    discipline). The standby scans it with the journal codec: a
    truncated or corrupted batch yields a clean record prefix and the
    connection resyncs, so corruption can only ever look like loss of
    a suffix."""

    offset: int
    data: bytes


@dataclass(frozen=True)
class SyncAck:
    """Standby → primary: every byte below ``offset`` is applied to the
    shadow state and written to the standby's local WAL — the seam the
    replica-acked durability tier gates winner acknowledgements on."""

    offset: int


Message = Union[
    Join, Request, Result, WorkResult, Cancel, Setup, Assign, RollAssign,
    Beacon, Steal, Emit, Refuse, RepHello, SyncFrom, WalStart, WalBatch,
    SyncAck,
]

_KINDS = {
    "join": Join,
    "request": Request,
    "result": Result,
    "wresult": WorkResult,
    "cancel": Cancel,
    "setup": Setup,
    "assign": Assign,
    "rassign": RollAssign,
    "beacon": Beacon,
    "steal": Steal,
    "emit": Emit,
    "refuse": Refuse,
    "rhello": RepHello,
    "syncfrom": SyncFrom,
    "walstart": WalStart,
    "walbatch": WalBatch,
    "syncack": SyncAck,
}


# ---------------------------------------------------------------------------
# binary fast-path codec (v1; see module docstring)
# ---------------------------------------------------------------------------

#: First byte of every JSON payload; no binary tag may equal it.
_JSON_OPEN = 0x7B  # ord("{")

#: Codec v1 tags. A future layout revision allocates NEW tags; these
#: five never change meaning.
_TAG_ASSIGN = 0xB1
_TAG_RESULT = 0xB2
_TAG_REFUSE = 0xB3
_TAG_CANCEL = 0xB4
_TAG_JOIN = 0xB5
#: Refuse carrying an admission retry-after hint (ISSUE 13). A separate
#: tag, not a new layout for 0xB3: v1 tags never change meaning, and an
#: old peer that has never heard of 0xB6 fails the unknown-tag check
#: loudly instead of misparsing a longer 0xB3.
_TAG_REFUSE_WAIT = 0xB6
# 0xB7 is reserved by tpuminter.journal for its packed settle record
# (same '{'-disjoint tag space, so a journal payload can never be
# confused with a wire message and vice versa).
#: WAL-shipping batch (tpuminter.replication): the one VARIABLE-length
#: binary message — ``tag ‖ offset:u64 ‖ raw journal bytes ‖ crc32``.
#: The raw bytes are shipped exactly as the journal flusher wrote them
#: (already length-prefixed + CRC'd per record), so no re-encoding
#: happens on the hot path. Distinct-length aliasing does not apply to
#: a variable-length kind; the trailing CRC32 alone carries the
#: corruption contract (any single-byte flip fails it).
_TAG_WALBATCH = 0xB8
#: Roll-budget dialect (module docstring): coordinator → worker
#: extranonce-unit dispatch and worker → coordinator sub-chunk progress.
#: New tags, not new layouts for 0xB1/0xB2 — v1 tags never change
#: meaning, and an old peer fails the unknown-tag check loudly.
_TAG_ASSIGN_ROLL = 0xB9
_TAG_BEACON = 0xBA
#: Pluggable-workload chunk/final answer (ISSUE 15): the second
#: VARIABLE-length binary message — ``tag ‖ job:u64 ‖ chunk:u64 ‖
#: wid:u8 ‖ searched:u64 ‖ fold payload ‖ crc32``. The payload is a
#: fold discipline's own tagged+CRC'd frame (tpuminter.workloads.folds,
#: tags 0xC1-0xC4 in this same process-wide namespace), shipped
#: opaquely; like WalBatch, the trailing envelope CRC carries the
#: corruption contract and distinct-length aliasing does not apply.
_TAG_WRESULT = 0xBB
#: Federation lease-epoch variants (ISSUE 18): a RollAssign/Beacon
#: carrying a non-zero ``lease_epoch``. NEW tags, not new layouts for
#: 0xB9/0xBA — v1 tags never change meaning, and only peers that sent
#: the aggregator hello (``Join.agg``) ever receive/emit them, so an
#: old peer never meets the unknown tag at all. The epoch is a u64 so
#: each layout lands on a total length no other fixed-size kind uses.
_TAG_ASSIGN_ROLL_E = 0xBC
_TAG_BEACON_E = 0xBD
#: Streaming-fold partial emission (ISSUE 20): the third VARIABLE-
#: length binary message — ``tag ‖ job:u64 ‖ seq:u64 ‖ covered:u64 ‖
#: total:u64 ‖ fold payload ‖ crc32``. Like WalBatch/WorkResult the
#: payload is an opaque already-CRC'd fold frame, the trailing envelope
#: CRC carries the corruption contract, and distinct-length aliasing
#: does not apply to a variable-length kind.
_TAG_EMIT = 0xBE

# Field layouts (little-endian). Every struct is a distinct total size
# (+4 CRC bytes), so a corrupted tag always fails the length check even
# before the CRC has its say — no kind can alias another.
_BIN_ASSIGN = struct.Struct("<BQQQQ")        # tag, job, chunk, lo, hi
_BIN_RESULT = struct.Struct("<BBQQ32sBQQ")   # tag, mode, job, nonce,
#                                              hash (u256 LE), found,
#                                              searched, chunk
_BIN_REFUSE = struct.Struct("<BQQ")          # tag, job, chunk
_BIN_REFUSE_WAIT = struct.Struct("<BQQI")    # tag, job, chunk, retry_ms
_BIN_CANCEL = struct.Struct("<BQ")           # tag, job
_BIN_JOIN = struct.Struct("<BBIQ16s")        # tag, flags, lanes, span,
#                                              backend (NUL-padded utf8)
_BIN_WALBATCH_HEAD = struct.Struct("<BQ")    # tag, offset (data follows)
_BIN_WRESULT_HEAD = struct.Struct("<BQQBQ")  # tag, job, chunk, wid,
#                                              searched (payload follows)
_BIN_EMIT_HEAD = struct.Struct("<BQQQQ")     # tag, job, seq, covered,
#                                              total (payload follows)
_BIN_ASSIGN_ROLL = struct.Struct("<BQQQI")   # tag, job, chunk,
#                                              extranonce0, count
_BIN_BEACON = struct.Struct("<BQQQQ32s")     # tag, job, chunk,
#                                              high_water, nonce,
#                                              hash (u256 LE)
_BIN_ASSIGN_ROLL_E = struct.Struct("<BQQQIQ")  # tag, job, chunk,
#                                                extranonce0, count,
#                                                lease_epoch
_BIN_BEACON_E = struct.Struct("<BQQQQ32sQ")  # tag, job, chunk,
#                                              high_water, nonce,
#                                              hash (u256 LE), lease_epoch
_CRC = struct.Struct("<I")

_BIN_BY_TAG = {
    _TAG_ASSIGN: _BIN_ASSIGN,
    _TAG_RESULT: _BIN_RESULT,
    _TAG_REFUSE: _BIN_REFUSE,
    _TAG_REFUSE_WAIT: _BIN_REFUSE_WAIT,
    _TAG_CANCEL: _BIN_CANCEL,
    _TAG_JOIN: _BIN_JOIN,
    _TAG_ASSIGN_ROLL: _BIN_ASSIGN_ROLL,
    _TAG_BEACON: _BIN_BEACON,
    _TAG_ASSIGN_ROLL_E: _BIN_ASSIGN_ROLL_E,
    _TAG_BEACON_E: _BIN_BEACON_E,
}

_JOIN_FLAG_BIN = 0x01   # Join.codec == "bin"
_JOIN_FLAG_ROLL = 0x02  # Join.roll (roll-budget dialect capability)

_MODE_TO_WIRE = {PowMode.MIN: 0, PowMode.TARGET: 1, PowMode.SCRYPT: 2}
_MODE_FROM_WIRE = {v: k for k, v in _MODE_TO_WIRE.items()}

_U64 = 1 << 64
_U256 = 1 << 256

#: Process-wide codec traffic counters (observability for loadgen/bench:
#: the json-vs-binary message mix is how the "16% JSON codec" profile
#: claim stays re-checkable from a shipped JSON). Snapshot-and-diff;
#: never reset in place.
codec_stats = {
    "json_encoded": 0,
    "binary_encoded": 0,
    "json_decoded": 0,
    "binary_decoded": 0,
}


def payload_is_binary(raw) -> bool:
    """True when an app payload uses the binary codec (first byte is a
    tag, not JSON's ``{``). The worker's negotiation hook: seeing one
    binary payload from the coordinator proves it decodes them."""
    return len(raw) > 0 and raw[0] != _JSON_OPEN


def _seal(body: bytes) -> bytes:
    return body + _CRC.pack(zlib.crc32(body))


def _encode_binary(msg: Message) -> Optional[bytes]:
    """Pack one hot message, or None when it cannot be represented
    (field out of the fixed-width range, non-hot kind) — the caller
    falls back to JSON, which represents everything."""
    if isinstance(msg, Assign):
        if not (0 <= msg.job_id < _U64 and 0 <= msg.chunk_id < _U64
                and 0 <= msg.lower < _U64 and 0 <= msg.upper < _U64):
            return None
        return _seal(_BIN_ASSIGN.pack(
            _TAG_ASSIGN, msg.job_id, msg.chunk_id, msg.lower, msg.upper
        ))
    if isinstance(msg, RollAssign):
        if not (0 <= msg.job_id < _U64 and 0 <= msg.chunk_id < _U64
                and 0 <= msg.extranonce0 < _U64
                and 0 < msg.count < (1 << 32)
                and 0 <= msg.lease_epoch < _U64):
            return None
        if msg.lease_epoch:
            return _seal(_BIN_ASSIGN_ROLL_E.pack(
                _TAG_ASSIGN_ROLL_E, msg.job_id, msg.chunk_id,
                msg.extranonce0, msg.count, msg.lease_epoch,
            ))
        return _seal(_BIN_ASSIGN_ROLL.pack(
            _TAG_ASSIGN_ROLL, msg.job_id, msg.chunk_id,
            msg.extranonce0, msg.count,
        ))
    if isinstance(msg, Beacon):
        if not (0 <= msg.job_id < _U64 and 0 <= msg.chunk_id < _U64
                and 0 <= msg.high_water < _U64 and 0 <= msg.nonce < _U64
                and 0 <= msg.hash_value < _U256
                and 0 <= msg.lease_epoch < _U64):
            return None
        if msg.lease_epoch:
            return _seal(_BIN_BEACON_E.pack(
                _TAG_BEACON_E, msg.job_id, msg.chunk_id, msg.high_water,
                msg.nonce, msg.hash_value.to_bytes(32, "little"),
                msg.lease_epoch,
            ))
        return _seal(_BIN_BEACON.pack(
            _TAG_BEACON, msg.job_id, msg.chunk_id, msg.high_water,
            msg.nonce, msg.hash_value.to_bytes(32, "little"),
        ))
    if isinstance(msg, Result):
        if not (0 <= msg.job_id < _U64 and 0 <= msg.nonce < _U64
                and 0 <= msg.hash_value < _U256
                and 0 <= msg.searched < _U64 and 0 <= msg.chunk_id < _U64):
            return None
        return _seal(_BIN_RESULT.pack(
            _TAG_RESULT, _MODE_TO_WIRE[msg.mode], msg.job_id, msg.nonce,
            msg.hash_value.to_bytes(32, "little"), 1 if msg.found else 0,
            msg.searched, msg.chunk_id,
        ))
    if isinstance(msg, Refuse):
        if not (0 <= msg.job_id < _U64 and 0 <= msg.chunk_id < _U64
                and 0 <= msg.retry_after_ms < (1 << 32)):
            return None
        if msg.retry_after_ms:
            return _seal(_BIN_REFUSE_WAIT.pack(
                _TAG_REFUSE_WAIT, msg.job_id, msg.chunk_id,
                msg.retry_after_ms,
            ))
        return _seal(_BIN_REFUSE.pack(_TAG_REFUSE, msg.job_id, msg.chunk_id))
    if isinstance(msg, Cancel):
        if not 0 <= msg.job_id < _U64:
            return None
        return _seal(_BIN_CANCEL.pack(_TAG_CANCEL, msg.job_id))
    if isinstance(msg, Join):
        backend = msg.backend.encode("utf-8", "strict")
        if (len(backend) > 16 or b"\x00" in backend
                or not 0 <= msg.lanes < (1 << 32)
                or not 0 <= msg.span < _U64
                or msg.codec not in ("json", "bin")
                or msg.workloads  # v1 layout predates the field: JSON
                or msg.agg):      # aggregator hello: JSON likewise
            return None
        flags = _JOIN_FLAG_BIN if msg.codec == "bin" else 0
        if msg.roll:
            flags |= _JOIN_FLAG_ROLL
        return _seal(_BIN_JOIN.pack(
            _TAG_JOIN, flags, msg.lanes, msg.span, backend
        ))
    if isinstance(msg, WalBatch):
        if not 0 <= msg.offset < _U64:
            return None
        return _seal(
            _BIN_WALBATCH_HEAD.pack(_TAG_WALBATCH, msg.offset)
            + bytes(msg.data)
        )
    if isinstance(msg, WorkResult):
        if not (0 <= msg.job_id < _U64 and 0 <= msg.chunk_id < _U64
                and 0 <= msg.wid < 256 and 0 <= msg.searched < _U64):
            return None
        return _seal(
            _BIN_WRESULT_HEAD.pack(
                _TAG_WRESULT, msg.job_id, msg.chunk_id, msg.wid,
                msg.searched,
            )
            + bytes(msg.payload)
        )
    if isinstance(msg, Emit):
        if not (0 <= msg.job_id < _U64 and 0 <= msg.seq < _U64
                and 0 <= msg.covered < _U64 and 0 <= msg.total < _U64):
            return None
        return _seal(
            _BIN_EMIT_HEAD.pack(
                _TAG_EMIT, msg.job_id, msg.seq, msg.covered, msg.total,
            )
            + bytes(msg.payload)
        )
    return None


def _decode_binary(raw) -> Message:
    n = len(raw)
    tag = raw[0]
    if tag == _TAG_WALBATCH:
        head = _BIN_WALBATCH_HEAD.size
        if n < head + _CRC.size:
            raise ProtocolError(f"walbatch payload truncated: {n} bytes")
        view = memoryview(raw)
        if (
            zlib.crc32(view[: n - _CRC.size])
            != _CRC.unpack_from(raw, n - _CRC.size)[0]
        ):
            raise ProtocolError("binary payload failed its checksum")
        _, offset = _BIN_WALBATCH_HEAD.unpack_from(raw)
        return WalBatch(offset, bytes(view[head : n - _CRC.size]))
    if tag == _TAG_WRESULT:
        head = _BIN_WRESULT_HEAD.size
        if n < head + _CRC.size:
            raise ProtocolError(f"wresult payload truncated: {n} bytes")
        view = memoryview(raw)
        if (
            zlib.crc32(view[: n - _CRC.size])
            != _CRC.unpack_from(raw, n - _CRC.size)[0]
        ):
            raise ProtocolError("binary payload failed its checksum")
        _, job_id, chunk_id, wid, searched = (
            _BIN_WRESULT_HEAD.unpack_from(raw)
        )
        return WorkResult(
            job_id, chunk_id, wid, searched,
            bytes(view[head : n - _CRC.size]),
        )
    if tag == _TAG_EMIT:
        head = _BIN_EMIT_HEAD.size
        if n < head + _CRC.size:
            raise ProtocolError(f"emit payload truncated: {n} bytes")
        view = memoryview(raw)
        if (
            zlib.crc32(view[: n - _CRC.size])
            != _CRC.unpack_from(raw, n - _CRC.size)[0]
        ):
            raise ProtocolError("binary payload failed its checksum")
        _, job_id, seq, covered, total = _BIN_EMIT_HEAD.unpack_from(raw)
        return Emit(
            job_id, seq, covered, total, bytes(view[head : n - _CRC.size]),
        )
    layout = _BIN_BY_TAG.get(tag)
    if layout is None:
        raise ProtocolError(f"unknown binary message tag {tag:#04x}")
    if n != layout.size + _CRC.size:
        raise ProtocolError(
            f"binary payload for tag {tag:#04x} is {n} bytes, "
            f"expected {layout.size + _CRC.size}"
        )
    view = memoryview(raw)
    if zlib.crc32(view[: layout.size]) != _CRC.unpack_from(raw, layout.size)[0]:
        raise ProtocolError("binary payload failed its checksum")
    try:
        if tag == _TAG_RESULT:
            _, mode, job_id, nonce, digest, found, searched, chunk_id = (
                _BIN_RESULT.unpack_from(raw)
            )
            if mode not in _MODE_FROM_WIRE or found not in (0, 1):
                raise ProtocolError("malformed binary result fields")
            return Result(
                job_id, _MODE_FROM_WIRE[mode], nonce,
                int.from_bytes(digest, "little"), bool(found),
                searched=searched, chunk_id=chunk_id,
            )
        if tag == _TAG_ASSIGN:
            _, job_id, chunk_id, lower, upper = _BIN_ASSIGN.unpack_from(raw)
            return Assign(job_id, chunk_id, lower, upper)
        if tag == _TAG_ASSIGN_ROLL:
            _, job_id, chunk_id, extranonce0, count = (
                _BIN_ASSIGN_ROLL.unpack_from(raw)
            )
            if count < 1:
                raise ProtocolError("roll assign must cover >= 1 extranonce")
            return RollAssign(job_id, chunk_id, extranonce0, count)
        if tag == _TAG_ASSIGN_ROLL_E:
            _, job_id, chunk_id, extranonce0, count, epoch = (
                _BIN_ASSIGN_ROLL_E.unpack_from(raw)
            )
            if count < 1:
                raise ProtocolError("roll assign must cover >= 1 extranonce")
            return RollAssign(
                job_id, chunk_id, extranonce0, count, lease_epoch=epoch
            )
        if tag == _TAG_BEACON:
            _, job_id, chunk_id, high_water, nonce, digest = (
                _BIN_BEACON.unpack_from(raw)
            )
            return Beacon(
                job_id, chunk_id, high_water, nonce,
                int.from_bytes(digest, "little"),
            )
        if tag == _TAG_BEACON_E:
            _, job_id, chunk_id, high_water, nonce, digest, epoch = (
                _BIN_BEACON_E.unpack_from(raw)
            )
            return Beacon(
                job_id, chunk_id, high_water, nonce,
                int.from_bytes(digest, "little"), lease_epoch=epoch,
            )
        if tag == _TAG_REFUSE:
            _, job_id, chunk_id = _BIN_REFUSE.unpack_from(raw)
            return Refuse(job_id, chunk_id)
        if tag == _TAG_REFUSE_WAIT:
            _, job_id, chunk_id, retry_ms = _BIN_REFUSE_WAIT.unpack_from(raw)
            return Refuse(job_id, chunk_id, retry_after_ms=retry_ms)
        if tag == _TAG_CANCEL:
            (_, job_id) = _BIN_CANCEL.unpack_from(raw)
            return Cancel(job_id)
        _, flags, lanes, span, backend = _BIN_JOIN.unpack_from(raw)
        return Join(
            backend=backend.rstrip(b"\x00").decode("utf-8"),
            lanes=lanes, span=span,
            codec="bin" if flags & _JOIN_FLAG_BIN else "json",
            roll=bool(flags & _JOIN_FLAG_ROLL),
        )
    except (struct.error, UnicodeDecodeError) as exc:
        raise ProtocolError(f"malformed binary message: {exc}") from exc


# ---------------------------------------------------------------------------
# cross-process shard seam frames (tpuminter.multiproc, ISSUE 19)
# ---------------------------------------------------------------------------
# These never ride the client/worker UDP port: they cross the per-host
# UNIX datagram channel between shard PROCESSES (and the supervisor).
# They share the process-wide '{'-disjoint tag namespace so a seam
# frame can never be mistaken for an app message, a journal record, or
# a fold payload; 0xD1+ is the block the workload registry left free.
# All five are VARIABLE-length kinds (ckey / raw datagram / encoded
# Result payloads follow the head), so like WalBatch the trailing CRC32
# alone carries the corruption contract.
_TAG_SEAM_FWD = 0xD1     # mis-steered datagram handoff (CONNECTs land
#                          on shard 0; the shard_of owner replays them
#                          through its own socket)
_TAG_SEAM_BIND = 0xD2    # rebind-registry gossip: shard k owns (ckey,
#                          client_job_id)
_TAG_SEAM_REBIND = 0xD3  # foreign shard -> home shard: a durable
#                          client re-submitted here; re-bind, don't
#                          duplicate the work
_TAG_SEAM_ANSWER = 0xD4  # home shard -> foreign shard: the durable
#                          winner's encoded Result (or a miss, payload
#                          empty + flag set: mint a fresh local job)
_TAG_SEAM_QUOTA = 0xD5   # shared admission state: cumulative per-ckey
#                          admission count gossip (idempotent under
#                          loss/reorder — receivers apply max-monotonic
#                          deltas)

_BIN_SEAM_FWD_HEAD = struct.Struct("<B4sH")     # tag, ip4, port
#                                                 (raw datagram follows)
_BIN_SEAM_BIND_HEAD = struct.Struct("<BBQ")     # tag, origin shard,
#                                                 client_job_id
#                                                 (ckey utf8 follows)
_BIN_SEAM_REBIND_HEAD = struct.Struct("<BBIQ")  # tag, origin shard,
#                                                 conn_id, client_job_id
#                                                 (ckey utf8 follows)
_BIN_SEAM_ANSWER_HEAD = struct.Struct("<BBIQ")  # tag, flags (bit0 =
#                                                 miss), conn_id,
#                                                 client_job_id
#                                                 (encoded Result follows)
_BIN_SEAM_QUOTA_HEAD = struct.Struct("<BBQ")    # tag, origin shard,
#                                                 cumulative admitted
#                                                 (ckey utf8 follows)

_SEAM_ANSWER_MISS = 0x01

#: ckeys longer than this never cross the seam (the coordinator's own
#: tables have no such bound, but a seam frame is one datagram and the
#: registry is a hint — an oversized key just stays shard-local).
SEAM_CKEY_MAX = 512


def encode_seam_fwd(addr, payload: bytes) -> bytes:
    """One mis-steered datagram, with its original source address, for
    the owning shard to replay as if the kernel had delivered it there."""
    import socket as _socket

    host, port = addr[0], addr[1]
    if not 0 <= port < (1 << 16):
        raise ProtocolError(f"seam fwd port out of range: {port}")
    try:
        ip4 = _socket.inet_aton(host)
    except OSError as exc:
        raise ProtocolError(f"seam fwd needs an IPv4 source: {host!r}") from exc
    return _seal(
        _BIN_SEAM_FWD_HEAD.pack(_TAG_SEAM_FWD, ip4, port) + bytes(payload)
    )


def _seam_ckey_bytes(ckey: str) -> bytes:
    raw = ckey.encode("utf-8", "strict")
    if not raw or len(raw) > SEAM_CKEY_MAX:
        raise ProtocolError(
            f"seam ckey must be 1..{SEAM_CKEY_MAX} utf-8 bytes"
        )
    return raw


def encode_seam_bind(origin: int, ckey: str, cjid: int) -> bytes:
    if not (0 <= origin < 256 and 0 <= cjid < _U64):
        raise ProtocolError("seam bind fields out of range")
    return _seal(
        _BIN_SEAM_BIND_HEAD.pack(_TAG_SEAM_BIND, origin, cjid)
        + _seam_ckey_bytes(ckey)
    )


def encode_seam_rebind(
    origin: int, conn_id: int, ckey: str, cjid: int
) -> bytes:
    if not (0 <= origin < 256 and 0 <= conn_id < (1 << 32)
            and 0 <= cjid < _U64):
        raise ProtocolError("seam rebind fields out of range")
    return _seal(
        _BIN_SEAM_REBIND_HEAD.pack(_TAG_SEAM_REBIND, origin, conn_id, cjid)
        + _seam_ckey_bytes(ckey)
    )


def encode_seam_answer(
    conn_id: int, cjid: int, payload: bytes, *, miss: bool = False
) -> bytes:
    if not (0 <= conn_id < (1 << 32) and 0 <= cjid < _U64):
        raise ProtocolError("seam answer fields out of range")
    if miss and payload:
        raise ProtocolError("a seam miss carries no payload")
    flags = _SEAM_ANSWER_MISS if miss else 0
    return _seal(
        _BIN_SEAM_ANSWER_HEAD.pack(_TAG_SEAM_ANSWER, flags, conn_id, cjid)
        + bytes(payload)
    )


def encode_seam_quota(origin: int, ckey: str, admitted: int) -> bytes:
    if not (0 <= origin < 256 and 0 <= admitted < _U64):
        raise ProtocolError("seam quota fields out of range")
    return _seal(
        _BIN_SEAM_QUOTA_HEAD.pack(_TAG_SEAM_QUOTA, origin, admitted)
        + _seam_ckey_bytes(ckey)
    )


_SEAM_HEADS = {
    _TAG_SEAM_FWD: _BIN_SEAM_FWD_HEAD,
    _TAG_SEAM_BIND: _BIN_SEAM_BIND_HEAD,
    _TAG_SEAM_REBIND: _BIN_SEAM_REBIND_HEAD,
    _TAG_SEAM_ANSWER: _BIN_SEAM_ANSWER_HEAD,
    _TAG_SEAM_QUOTA: _BIN_SEAM_QUOTA_HEAD,
}


def decode_seam(raw) -> tuple:
    """Decode one seam frame to a ``(kind, ...)`` tuple:

    - ``("fwd", (host, port), payload)``
    - ``("bind", origin, ckey, cjid)``
    - ``("rebind", origin, conn_id, ckey, cjid)``
    - ``("answer", miss, conn_id, cjid, payload)``
    - ``("quota", origin, ckey, admitted)``

    Raises :class:`ProtocolError` on truncation, CRC failure, unknown
    tags, or malformed ckeys — the receiving shard drops the frame (the
    seam is a hint channel with miss fallbacks; it must never crash a
    serve loop)."""
    import socket as _socket

    n = len(raw)
    if n < 1:
        raise ProtocolError("empty seam frame")
    head = _SEAM_HEADS.get(raw[0])
    if head is None:
        raise ProtocolError(f"unknown seam frame tag {raw[0]:#04x}")
    if n < head.size + _CRC.size:
        raise ProtocolError(f"seam frame truncated: {n} bytes")
    view = memoryview(raw)
    if (
        zlib.crc32(view[: n - _CRC.size])
        != _CRC.unpack_from(raw, n - _CRC.size)[0]
    ):
        raise ProtocolError("seam frame failed its checksum")
    tail = bytes(view[head.size : n - _CRC.size])
    tag = raw[0]
    try:
        if tag == _TAG_SEAM_FWD:
            _, ip4, port = head.unpack_from(raw)
            return ("fwd", (_socket.inet_ntoa(ip4), port), tail)
        if tag == _TAG_SEAM_BIND:
            _, origin, cjid = head.unpack_from(raw)
            return ("bind", origin, tail.decode("utf-8"), cjid)
        if tag == _TAG_SEAM_REBIND:
            _, origin, conn_id, cjid = head.unpack_from(raw)
            return ("rebind", origin, conn_id, tail.decode("utf-8"), cjid)
        if tag == _TAG_SEAM_ANSWER:
            _, flags, conn_id, cjid = head.unpack_from(raw)
            return (
                "answer", bool(flags & _SEAM_ANSWER_MISS), conn_id, cjid,
                tail,
            )
        _, origin, admitted = head.unpack_from(raw)
        return ("quota", origin, tail.decode("utf-8"), admitted)
    except (struct.error, UnicodeDecodeError) as exc:
        raise ProtocolError(f"malformed seam frame: {exc}") from exc


def _request_obj(msg: Request) -> dict:
    obj = {
        "kind": "request",
        "job_id": msg.job_id,
        "mode": msg.mode.value,
        "lower": msg.lower,
        "upper": msg.upper,
        "chunk_id": msg.chunk_id,
    }
    if msg.data:
        obj["data"] = msg.data.hex()
    if msg.header is not None:
        obj["header"] = msg.header.hex()
    if msg.target is not None:
        obj["target"] = f"{msg.target:x}"
    if msg.rolled:
        obj["cb_prefix"] = msg.coinbase_prefix.hex()
        obj["cb_suffix"] = msg.coinbase_suffix.hex()
        obj["en_size"] = msg.extranonce_size
        obj["branch"] = [sib.hex() for sib in msg.branch]
        obj["nonce_bits"] = msg.nonce_bits
    if msg.client_key:
        obj["ckey"] = msg.client_key
    if msg.workload:
        obj["wl"] = msg.workload
    if msg.stream:
        obj["strm"] = 1
    return obj


def _request_from_obj(obj: dict) -> Request:
    return Request(
        job_id=int(obj["job_id"]),
        mode=PowMode(obj["mode"]),
        lower=int(obj["lower"]),
        upper=int(obj["upper"]),
        data=bytes.fromhex(obj["data"]) if "data" in obj else b"",
        header=bytes.fromhex(obj["header"]) if "header" in obj else None,
        target=int(obj["target"], 16) if "target" in obj else None,
        chunk_id=int(obj.get("chunk_id", 0)),
        coinbase_prefix=(
            bytes.fromhex(obj["cb_prefix"]) if "cb_prefix" in obj else None
        ),
        coinbase_suffix=bytes.fromhex(obj.get("cb_suffix", "")),
        extranonce_size=int(obj.get("en_size", 4)),
        branch=tuple(bytes.fromhex(s) for s in obj.get("branch", [])),
        nonce_bits=int(obj.get("nonce_bits", 32)),
        client_key=str(obj.get("ckey", "")),
        workload=str(obj.get("wl", "")),
        stream=bool(obj.get("strm", 0)),
    )


#: Public names for the Request ↔ JSON-object codec: the journal
#: (``tpuminter.journal``) persists job templates through the same
#: codec the wire uses, so replayed Requests are bit-equal to received
#: ones.
request_to_obj = _request_obj
request_from_obj = _request_from_obj


def encode_msg(msg: Message, *, binary: bool = False) -> bytes:
    """Serialize an app message to an LSP payload.

    ``binary=True`` uses the struct-packed fast path for the hot kinds
    (Assign/Result/Refuse/Cancel/Join) when every field fits the fixed
    widths, falling back to JSON otherwise — callers opt in per
    connection after negotiation (module docstring), never blindly.
    """
    if binary:
        raw = _encode_binary(msg)
        if raw is not None:
            codec_stats["binary_encoded"] += 1
            return raw
    codec_stats["json_encoded"] += 1
    if isinstance(msg, Join):
        obj = {"kind": "join", "backend": msg.backend, "lanes": msg.lanes,
               "span": msg.span}
        if msg.codec != "json":
            obj["codec"] = msg.codec
        if msg.roll:
            obj["roll"] = 1
        if msg.workloads:
            obj["wl"] = list(msg.workloads)
        if msg.agg:
            obj["agg"] = msg.agg
    elif isinstance(msg, Request):
        obj = _request_obj(msg)
    elif isinstance(msg, Setup):
        obj = {"kind": "setup", "request": _request_obj(msg.request)}
    elif isinstance(msg, Assign):
        obj = {
            "kind": "assign",
            "job_id": msg.job_id,
            "chunk_id": msg.chunk_id,
            "lower": msg.lower,
            "upper": msg.upper,
        }
    elif isinstance(msg, RollAssign):
        obj = {
            "kind": "rassign",
            "job_id": msg.job_id,
            "chunk_id": msg.chunk_id,
            "e0": msg.extranonce0,
            "count": msg.count,
        }
        if msg.lease_epoch:
            obj["le"] = msg.lease_epoch
    elif isinstance(msg, Beacon):
        obj = {
            "kind": "beacon",
            "job_id": msg.job_id,
            "chunk_id": msg.chunk_id,
            "hw": msg.high_water,
            "nonce": msg.nonce,
            "hash": f"{msg.hash_value:x}",
        }
        if msg.lease_epoch:
            obj["le"] = msg.lease_epoch
    elif isinstance(msg, Steal):
        obj = {"kind": "steal"}
        if msg.job_id:
            obj["job_id"] = msg.job_id
    elif isinstance(msg, Emit):
        obj = {
            "kind": "emit",
            "job_id": msg.job_id,
            "seq": msg.seq,
            "cov": msg.covered,
            "tot": msg.total,
            "wp": bytes(msg.payload).hex(),
        }
    elif isinstance(msg, Refuse):
        obj = {"kind": "refuse", "job_id": msg.job_id, "chunk_id": msg.chunk_id}
        if msg.retry_after_ms:
            obj["retry_after_ms"] = msg.retry_after_ms
    elif isinstance(msg, Result):
        obj = {
            "kind": "result",
            "job_id": msg.job_id,
            "mode": msg.mode.value,
            "nonce": msg.nonce,
            "hash": f"{msg.hash_value:x}",
            "found": msg.found,
            "searched": msg.searched,
            "chunk_id": msg.chunk_id,
        }
    elif isinstance(msg, WorkResult):
        obj = {
            "kind": "wresult",
            "job_id": msg.job_id,
            "chunk_id": msg.chunk_id,
            "wid": msg.wid,
            "searched": msg.searched,
            "wp": bytes(msg.payload).hex(),
        }
    elif isinstance(msg, Cancel):
        obj = {"kind": "cancel", "job_id": msg.job_id}
    elif isinstance(msg, RepHello):
        obj = {"kind": "rhello", "epoch": msg.epoch}
    elif isinstance(msg, SyncFrom):
        obj = {
            "kind": "syncfrom", "off": msg.offset,
            "start": msg.last_start, "crc": msg.crc,
        }
    elif isinstance(msg, WalStart):
        obj = {"kind": "walstart", "off": msg.offset}
    elif isinstance(msg, WalBatch):
        # compat long tail only — the shipper always speaks binary
        obj = {"kind": "walbatch", "off": msg.offset,
               "data": bytes(msg.data).hex()}
    elif isinstance(msg, SyncAck):
        obj = {"kind": "syncack", "off": msg.offset}
    else:
        raise ProtocolError(f"not an app message: {msg!r}")
    return json.dumps(obj, separators=(",", ":")).encode()


def decode_msg(raw) -> Message:
    """Parse an LSP payload back into an app message.

    Accepts ``bytes`` or the LSP layer's zero-copy ``memoryview``
    directly: the binary fast path unpacks fields in place with no
    payload copy at all, and only the JSON long tail materializes the
    view (``json.loads`` does not take buffers)."""
    if len(raw) == 0:
        raise ProtocolError("empty payload")
    if raw[0] != _JSON_OPEN:
        msg = _decode_binary(raw)
        codec_stats["binary_decoded"] += 1
        return msg
    codec_stats["json_decoded"] += 1
    try:
        obj = json.loads(
            raw if isinstance(raw, (bytes, bytearray, str)) else bytes(raw)
        )
    except (ValueError, UnicodeDecodeError) as exc:
        raise ProtocolError(f"payload is not JSON: {exc}") from exc
    if not isinstance(obj, dict) or obj.get("kind") not in _KINDS:
        raise ProtocolError(f"unknown message kind: {obj!r}")
    kind = obj["kind"]
    try:
        if kind == "join":
            return Join(
                backend=str(obj.get("backend", "cpu")),
                lanes=int(obj.get("lanes", 1)),
                span=int(obj.get("span", 0)),
                codec=str(obj.get("codec", "json")),
                roll=bool(obj.get("roll", 0)),
                workloads=tuple(str(w) for w in obj.get("wl", [])),
                agg=str(obj.get("agg", "")),
            )
        if kind == "request":
            return _request_from_obj(obj)
        if kind == "setup":
            req = obj["request"]
            if not isinstance(req, dict):
                raise ProtocolError("setup message needs a request object")
            return Setup(request=_request_from_obj(req))
        if kind == "assign":
            return Assign(
                job_id=int(obj["job_id"]),
                chunk_id=int(obj["chunk_id"]),
                lower=int(obj["lower"]),
                upper=int(obj["upper"]),
            )
        if kind == "rassign":
            count = int(obj["count"])
            if count < 1:
                raise ProtocolError("roll assign must cover >= 1 extranonce")
            return RollAssign(
                job_id=int(obj["job_id"]),
                chunk_id=int(obj["chunk_id"]),
                extranonce0=int(obj["e0"]),
                count=count,
                lease_epoch=int(obj.get("le", 0)),
            )
        if kind == "beacon":
            return Beacon(
                job_id=int(obj["job_id"]),
                chunk_id=int(obj["chunk_id"]),
                high_water=int(obj["hw"]),
                nonce=int(obj["nonce"]),
                hash_value=int(obj["hash"], 16),
                lease_epoch=int(obj.get("le", 0)),
            )
        if kind == "steal":
            return Steal(job_id=int(obj.get("job_id", 0)))
        if kind == "emit":
            return Emit(
                job_id=int(obj["job_id"]),
                seq=int(obj["seq"]),
                covered=int(obj["cov"]),
                total=int(obj["tot"]),
                payload=bytes.fromhex(obj.get("wp", "")),
            )
        if kind == "refuse":
            return Refuse(
                job_id=int(obj["job_id"]), chunk_id=int(obj["chunk_id"]),
                retry_after_ms=int(obj.get("retry_after_ms", 0)),
            )
        if kind == "rhello":
            return RepHello(epoch=int(obj["epoch"]))
        if kind == "syncfrom":
            return SyncFrom(
                offset=int(obj["off"]), last_start=int(obj.get("start", -1)),
                crc=int(obj.get("crc", 0)),
            )
        if kind == "walstart":
            return WalStart(offset=int(obj["off"]))
        if kind == "walbatch":
            return WalBatch(
                offset=int(obj["off"]), data=bytes.fromhex(obj["data"])
            )
        if kind == "syncack":
            return SyncAck(offset=int(obj["off"]))
        if kind == "result":
            return Result(
                job_id=int(obj["job_id"]),
                mode=PowMode(obj["mode"]),
                nonce=int(obj["nonce"]),
                hash_value=int(obj["hash"], 16),
                found=bool(obj["found"]),
                searched=int(obj.get("searched", 0)),
                chunk_id=int(obj.get("chunk_id", 0)),
            )
        if kind == "wresult":
            return WorkResult(
                job_id=int(obj["job_id"]),
                chunk_id=int(obj["chunk_id"]),
                wid=int(obj["wid"]),
                searched=int(obj.get("searched", 0)),
                payload=bytes.fromhex(obj.get("wp", "")),
            )
        return Cancel(job_id=int(obj["job_id"]))
    except (KeyError, ValueError, TypeError) as exc:
        raise ProtocolError(f"malformed {kind} message: {exc}") from exc
