"""SHA-256 on device: jnp u32 vectors, vmapped over nonce batches.

The TPU-first design (SURVEY.md §7 stage 3-4; north-star BASELINE.json:5):

- **Midstate specialization.** A mining message is constant except for a
  few nonce bytes near the end. All 64-byte blocks before the first
  nonce-bearing word are compressed ONCE on the host
  (``chain.midstate``-style); the device only compresses the remaining
  "tail" block(s) per candidate. For an 80-byte Bitcoin header that is 1
  tail block + the 1-block second hash — 2 compressions per nonce
  instead of 3.
- **Trace-time message templates.** Where the nonce bytes land in the
  tail (block, word, intra-word shift) depends only on the job, never on
  the candidate, so a :class:`NonceTemplate` carries those positions as
  *Python ints* and the jitted batch functions close over them — all
  indexing is static, XLA sees straight-line u32 ALU code it can tile
  onto the VPU. No dynamic shapes, no data-dependent control flow.
- **64-bit nonces as u32 pairs.** The toy dialect's nonce space is
  2^64; JAX's default (and TPU-native) int width is 32, so nonces travel
  as ``(hi, lo)`` u32 vectors and 64-bit/256-bit comparisons are
  lexicographic over u32 lanes (:func:`lex_le`, :func:`lex_argmin`).

Everything is pure; no global state. Host-side reference semantics live
in ``tpuminter.chain`` (verified against hashlib / the genesis block);
the equivalence tests in tests/test_ops_sha256.py pin this module to it.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpuminter.chain import SHA256_H0, SHA256_K, sha256_compress

__all__ = [
    "compress",
    "NonceTemplate",
    "toy_template",
    "header_template",
    "sha256_batch",
    "double_sha256_header_batch",
    "HEADER_NONCE_POSITIONS",
    "HEADER_TAIL_PAD",
    "header_digest_dyn",
    "header_e60_e61_dyn",
    "byteswap32",
    "hash_words_be",
    "lex_le",
    "lex_argmin",
    "target_to_words",
    "digest_to_int",
]

_K = tuple(np.uint32(k) for k in SHA256_K)
_K_ARR = np.array(SHA256_K, dtype=np.uint32)
_H0 = np.array(SHA256_H0, dtype=np.uint32)


def _rotr(x: jnp.ndarray, n: int) -> jnp.ndarray:
    # TPUs have no rotate instruction; XLA lowers this shift/or pair onto
    # the VPU (pallas_guide: same form the hand kernel uses).
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _round_unroll() -> bool:
    """Unroll the 64 rounds at trace time only where it pays off.

    TPU: XLA handles the flat ~7k-op graph fine and straight-line code
    schedules best. CPU (the CI backend): compile time scales hard with
    unrolled program size — one or two compressions inside a scan body
    compile in ~3 s and run ~30x faster than the scanned form (the
    shared-schedule sweep, PERF.md §Round 14), but stacking their output
    into a trailing-axis array or chaining ~10 compressions straight-line
    (the roll: ~40 s/job; the tracking step: 15-42 s) blows the compile
    budget. The scanned default stays right for this general-purpose
    entry point, which callers embed many-at-a-time; the sweep-shaped
    winners opt into the unrolled symbolic form explicitly
    (:func:`header_e60_e61_dyn`).
    """
    return jax.default_backend() not in ("cpu",)


def compress(
    state: jnp.ndarray, block: jnp.ndarray, unroll: bool | None = None
) -> jnp.ndarray:
    """One SHA-256 compression: ``state (..., 8) u32``, ``block (..., 16)
    u32`` → ``(..., 8) u32``, elementwise over leading batch dims.

    ≡ ``chain.sha256_compress`` (FIPS 180-4). The message schedule is
    computed on the fly inside the round loop via the classic rolling
    16-word window (w[i+16] = w[i] + σ0(w[i+1]) + w[i+9] + σ1(w[i+14])),
    which keeps the scanned form O(1) state; the unrolled form emits the
    same dataflow flattened.

    ``unroll`` overrides the backend default (:func:`_round_unroll`).
    Callers that embed MANY compressions in one program (the scrypt
    PBKDF2 walls: 21 of them) pass ``False`` — 21 × ~7k unrolled ops
    bloat the XLA program into minutes of compile time for a stage
    that is ~2% of scrypt's runtime.
    """
    if _round_unroll() if unroll is None else unroll:
        return _compress_unrolled(state, block)
    return _compress_scanned(state, block)


def _schedule_next(win: jnp.ndarray) -> jnp.ndarray:
    """w[i+16] from the window w[i..i+15] (last axis)."""
    s0 = (
        _rotr(win[..., 1], 7) ^ _rotr(win[..., 1], 18) ^ (win[..., 1] >> np.uint32(3))
    )
    s1 = (
        _rotr(win[..., 14], 17)
        ^ _rotr(win[..., 14], 19)
        ^ (win[..., 14] >> np.uint32(10))
    )
    return win[..., 0] + s0 + win[..., 9] + s1


def _one_round(vars8, k_plus_w):
    a, b, c, d, e, f, g, h = vars8
    s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
    ch = (e & f) ^ (~e & g)
    t1 = h + s1 + ch + k_plus_w
    s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
    maj = (a & b) ^ (a & c) ^ (b & c)
    t2 = s0 + maj
    return (t1 + t2, a, b, c, d + t1, e, f, g)


def _compress_unrolled(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    w = [block[..., i] for i in range(16)]
    for i in range(16, 64):
        s0 = _rotr(w[i - 15], 7) ^ _rotr(w[i - 15], 18) ^ (w[i - 15] >> np.uint32(3))
        s1 = _rotr(w[i - 2], 17) ^ _rotr(w[i - 2], 19) ^ (w[i - 2] >> np.uint32(10))
        w.append(w[i - 16] + s0 + w[i - 7] + s1)
    vars8 = tuple(state[..., i] for i in range(8))
    for i in range(64):
        vars8 = _one_round(vars8, _K[i] + w[i])
    return jnp.stack(
        [state[..., i] + vars8[i] for i in range(8)], axis=-1
    )


def _compress_scanned(state: jnp.ndarray, block: jnp.ndarray) -> jnp.ndarray:
    def step(carry, k):
        vars8, win = carry
        vars8 = _one_round(vars8, k + win[..., 0])
        win = jnp.concatenate(
            [win[..., 1:], _schedule_next(win)[..., None]], axis=-1
        )
        return (vars8, win), None

    init = (tuple(state[..., i] for i in range(8)), block)
    (vars8, _), _ = jax.lax.scan(step, init, jnp.asarray(_K_ARR))
    return jnp.stack([state[..., i] + vars8[i] for i in range(8)], axis=-1)


# ---------------------------------------------------------------------------
# Nonce templates: host-side message planning
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class NonceTemplate:
    """A padded SHA-256 message with a nonce-shaped hole.

    ``midstate``: state after the constant prefix blocks (8 u32).
    ``tail``: the remaining block(s), nonce bytes zeroed ((n, 16) u32).
    ``positions``: one entry per nonce byte —
    ``(block, word, word_shift, nonce_shift)`` meaning
    ``tail[block, word] |= ((nonce >> nonce_shift) & 0xFF) << word_shift``.
    All entries are Python ints: jitted code closes over them as static
    constants (this dataclass is hashable ⇒ usable as a jit cache key).
    ``double``: apply a second SHA-256 over the 32-byte digest (Bitcoin).
    """

    midstate: Tuple[int, ...]
    tail: Tuple[Tuple[int, ...], ...]
    positions: Tuple[Tuple[int, int, int, int], ...]
    double: bool = False

    @property
    def n_tail_blocks(self) -> int:
        return len(self.tail)

    def tail_array(self) -> np.ndarray:
        return np.array(self.tail, dtype=np.uint32)

    def midstate_array(self) -> np.ndarray:
        return np.array(self.midstate, dtype=np.uint32)


def _pad(message_len: int) -> bytes:
    """FIPS 180-4 padding for a ``message_len``-byte message."""
    pad = b"\x80" + b"\x00" * ((55 - message_len) % 64)
    return pad + struct.pack(">Q", message_len * 8)


def _build_template(
    message_with_hole: bytes,
    hole_offset: int,
    byte_map: Sequence[Tuple[int, int]],
    *,
    double: bool,
) -> NonceTemplate:
    """Plan a template: ``byte_map[j] = (offset_delta, nonce_shift)`` puts
    ``(nonce >> nonce_shift) & 0xFF`` at ``hole_offset + offset_delta``."""
    padded = message_with_hole + _pad(len(message_with_hole))
    assert len(padded) % 64 == 0
    first_hole_block = min(hole_offset + d for d, _ in byte_map) // 64
    state = tuple(SHA256_H0)
    for b in range(first_hole_block):
        state = sha256_compress(state, padded[b * 64 : (b + 1) * 64])
    tail_bytes = padded[first_hole_block * 64 :]
    tail = tuple(
        struct.unpack(">16I", tail_bytes[b * 64 : (b + 1) * 64])
        for b in range(len(tail_bytes) // 64)
    )
    positions = []
    for offset_delta, nonce_shift in byte_map:
        off = hole_offset + offset_delta - first_hole_block * 64
        positions.append((off // 64, (off % 64) // 4, 24 - 8 * (off % 4), nonce_shift))
    return NonceTemplate(
        midstate=state, tail=tail, positions=tuple(positions), double=double
    )


def toy_template(data: bytes) -> NonceTemplate:
    """Template for the toy dialect: SHA-256(data ‖ nonce_be8), any data
    length (≡ ``chain.toy_hash``). The 8 big-endian nonce bytes may be
    unaligned and may straddle a block boundary; the byte map handles
    both."""
    message = data + b"\x00" * 8
    byte_map = [(j, 56 - 8 * j) for j in range(8)]
    return _build_template(message, len(data), byte_map, double=False)


def header_template(header80: bytes) -> NonceTemplate:
    """Template for Bitcoin: double-SHA-256 over an 80-byte header whose
    final 4 bytes are the little-endian nonce (≡ ``BlockHeader`` +
    ``chain.dsha256``). One tail block; midstate covers bytes [0, 64)."""
    if len(header80) != 80:
        raise ValueError(f"header must be 80 bytes, got {len(header80)}")
    message = header80[:76] + b"\x00" * 4
    byte_map = [(j, 8 * j) for j in range(4)]  # little-endian
    return _build_template(message, 76, byte_map, double=True)


# ---------------------------------------------------------------------------
# Batched hashing
# ---------------------------------------------------------------------------

def _inject_nonces(
    template: NonceTemplate, nonce_hi: jnp.ndarray, nonce_lo: jnp.ndarray
) -> jnp.ndarray:
    """Broadcast the tail template over the batch and OR in the nonce
    bytes at their static positions → ``(N, n_blocks, 16) u32``."""
    n = nonce_lo.shape[0]
    tail = jnp.broadcast_to(
        jnp.asarray(template.tail_array()), (n,) + (template.n_tail_blocks, 16)
    )
    for block, word, word_shift, nonce_shift in template.positions:
        src = nonce_hi if nonce_shift >= 32 else nonce_lo
        shift = nonce_shift - 32 if nonce_shift >= 32 else nonce_shift
        byte = (src >> np.uint32(shift)) & np.uint32(0xFF)
        tail = tail.at[:, block, word].add(byte << np.uint32(word_shift))
    return tail


def sha256_batch(
    template: NonceTemplate, nonce_hi: jnp.ndarray, nonce_lo: jnp.ndarray
) -> jnp.ndarray:
    """Digests for a batch of nonces: ``(N,) u32 × 2 → (N, 8) u32``
    (digest as big-endian u32 words, i.e. ``struct.unpack('>8I', digest)``).

    Applies the template's second hash when ``template.double``.
    """
    n = nonce_lo.shape[0]
    tail = _inject_nonces(template, nonce_hi, nonce_lo)
    state = jnp.broadcast_to(jnp.asarray(template.midstate_array()), (n, 8))
    for b in range(template.n_tail_blocks):
        state = compress(state, tail[:, b, :])
    if template.double:
        # second message: 32-byte digest ‖ 0x80 ‖ zeros ‖ len(256 bits)
        block2 = jnp.concatenate(
            [
                state,
                jnp.broadcast_to(
                    jnp.asarray(
                        np.array(
                            [0x80000000, 0, 0, 0, 0, 0, 0, 256], dtype=np.uint32
                        )
                    ),
                    (n, 8),
                ),
            ],
            axis=-1,
        )
        state = compress(jnp.broadcast_to(jnp.asarray(_H0), (n, 8)), block2)
    return state


def double_sha256_header_batch(
    template: NonceTemplate, nonces: jnp.ndarray
) -> jnp.ndarray:
    """Convenience wrapper for header mining: u32 nonce vector → (N, 8)
    digest words of double-SHA-256(header with that nonce)."""
    zeros = jnp.zeros_like(nonces)
    return sha256_batch(template, zeros, nonces)


# ---------------------------------------------------------------------------
# Dynamic header hashing (the on-device extranonce-roll consumer)
# ---------------------------------------------------------------------------

#: nonce byte positions in an 80-byte header's tail block: little-endian
#: u32 at bytes 76..80, i.e. word 3 of the second block (what
#: ``header_template`` computes; pinned by tests against it)
HEADER_NONCE_POSITIONS: Tuple[Tuple[int, int, int, int], ...] = (
    (0, 3, 24, 0),
    (0, 3, 16, 8),
    (0, 3, 8, 16),
    (0, 3, 0, 24),
)

#: constant schedule words 4..15 of an 80-byte header's tail block
#: (FIPS 180-4 padding for an 80-byte message: 0x80 then the 640-bit len)
HEADER_TAIL_PAD: Tuple[int, ...] = (0x80000000, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 640)


def header_digest_dyn(
    midstate8: jnp.ndarray, tailw3: jnp.ndarray, nonces: jnp.ndarray
) -> jnp.ndarray:
    """Double-SHA-256 digests for a header whose midstate and variable
    tail words are *runtime values* (u32 arrays of shape (..., 8) and
    (..., 3)), not trace-time constants: ``(N,) u32 nonces → (N, 8)
    digest words`` — or, batched over roll rows, ``(B, 8) midstates +
    (B, 3) tails + (B, N) nonces → (B, N, 8)``: row ``i``'s nonces are
    hashed under row ``i``'s header. The batched form is the jnp engine
    of the batched rolled sweep (``tpuminter.rolled``): one dispatch
    sweeps every row of a ``make_extranonce_roll_batch`` output.

    This is the hash the on-device extranonce roll feeds
    (``ops.merkle.make_extranonce_roll_batch`` produces exactly this
    ``(midstate, tail_words)`` pair for each extranonce, BASELINE.json:
    9-10): one compiled program serves every extranonce — and every
    header-mining job — because nothing job-specific is baked in.
    ``tailw3`` is ``(merkle_root word 7, time word, bits word)``, the
    three header tail words before the nonce. ≡ ``double_sha256_header_
    batch(header_template(header), nonces)`` for the equivalent header
    (tests pin them equal, batched rows included).

    Built on :func:`compress` (scanned on CPU, unrolled on TPU): this
    full-digest form feeds trailing-axis (N, 8) folds, and stacking the
    unrolled symbolic form's separate word values into that layout is a
    measured CPU loss (0.2-4x runtime at 15-42 s compile, PERF.md §Round
    14 rejection) — the truncated candidate twin
    (:func:`header_e60_e61_dyn`), which never materializes the stack, is
    where the unrolled form wins 34x. The little-endian nonce bytes at
    header offset 76 read as a big-endian schedule word are simply
    ``byteswap(nonce)``.
    """
    shape = nonces.shape
    tail = jnp.concatenate(
        [
            jnp.broadcast_to(tailw3[..., None, :], shape + (3,)),
            byteswap32(nonces)[..., None],
            jnp.broadcast_to(
                jnp.asarray(np.array(HEADER_TAIL_PAD, dtype=np.uint32)),
                shape + (12,),
            ),
        ],
        axis=-1,
    )
    state = compress(
        jnp.broadcast_to(midstate8[..., None, :], shape + (8,)), tail
    )
    block2 = jnp.concatenate(
        [
            state,
            jnp.broadcast_to(
                jnp.asarray(
                    np.array([0x80000000, 0, 0, 0, 0, 0, 0, 256], dtype=np.uint32)
                ),
                shape + (8,),
            ),
        ],
        axis=-1,
    )
    return compress(jnp.broadcast_to(jnp.asarray(_H0), shape + (8,)), block2)


def header_e60_e61_dyn(
    midstate8: jnp.ndarray, tailw3: jnp.ndarray, nonces: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``(e60, e61)`` of the double-SHA for one dynamic header row — the
    shared-schedule sweep engine (ISSUE 16): digest word 7 is
    ``SHA256_H0[7] + e60`` and word 6 is ``symbolic.DIGEST6_BIAS + e61``,
    so the candidate test over the hash's top 64 bits needs nothing else
    (bit-for-bit ≡ the same test on :func:`header_digest_dyn` output;
    tier-1 pins it).

    Unlike :func:`header_digest_dyn` this IS built on the symbolic
    unrolled form — the AsicBoost discipline (arxiv 1604.00575) expressed
    as lane-level common-subexpression scheduling: every nonce of the
    sweep collides on ``(midstate, merkle word 7, time, bits)``, so the
    nonce-free rounds 0-2, schedule words w16/w17, and the scalar parts
    of w18/w19 stay 0-d (computed once per row, not per lane), constants
    fold at trace time, the second compression truncates at round 61,
    and — decisively on this backend — the straight-line rounds dodge the
    per-round ``lax.scan`` overhead that dominates the scanned compress
    at sweep widths (measured 34x at 8x256, ~3 s one-time compile per
    (width, cand_bits) shape; PERF.md §Round 14). The inputs are 0-d u32
    scalars + a (N,) nonce vector: exactly one row of the batched rolled
    sweep's ``lax.scan``.
    """
    from tpuminter.ops import symbolic as sym

    mid = [midstate8[..., i] for i in range(8)]
    block = [
        tailw3[..., 0], tailw3[..., 1], tailw3[..., 2],
        byteswap32(nonces), *HEADER_TAIL_PAD,
    ]
    return sym.hash_sym_e60_e61(mid, [block], (), 0, 0)


# ---------------------------------------------------------------------------
# 256-bit comparisons in u32 lanes
# ---------------------------------------------------------------------------

def byteswap32(x: jnp.ndarray) -> jnp.ndarray:
    """Per-lane u32 byte swap (big-endian ↔ little-endian word reads);
    shared by the hash-value converters here and the scrypt word seams."""
    return (
        ((x & np.uint32(0x000000FF)) << np.uint32(24))
        | ((x & np.uint32(0x0000FF00)) << np.uint32(8))
        | ((x & np.uint32(0x00FF0000)) >> np.uint32(8))
        | ((x & np.uint32(0xFF000000)) >> np.uint32(24))
    )


def hash_words_be(digest_words: jnp.ndarray) -> jnp.ndarray:
    """Digest words → the 256-bit *hash value* as big-endian u32 words,
    most significant first: Bitcoin interprets the digest as a
    little-endian integer, so word j = byteswap(digest_word[7-j])."""
    return byteswap32(digest_words[..., ::-1])


def lex_le(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Lexicographic ``a <= b`` over the last axis (msb-first u32 words);
    broadcasts, returns bool with the last axis reduced."""
    lt = jnp.zeros(jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1]), dtype=bool)
    eq = jnp.ones_like(lt)
    for k in range(a.shape[-1]):
        ak, bk = a[..., k], b[..., k]
        lt = lt | (eq & (ak < bk))
        eq = eq & (ak == bk)
    return lt | eq


def lex_argmin(words: jnp.ndarray) -> jnp.ndarray:
    """Index of the lexicographic minimum of ``words (N, W)`` (msb-first
    u32 words); ties resolve to the lowest index (= lowest nonce, the
    coordinator's fold order). O(W) min+mask passes — no 64-bit math."""
    n, w = words.shape
    mask = jnp.ones((n,), dtype=bool)
    big = np.uint32(0xFFFFFFFF)
    for k in range(w):
        col = jnp.where(mask, words[:, k], big)
        mask = mask & (col == col.min())
    return jnp.argmax(mask)


# ---------------------------------------------------------------------------
# Host-side converters
# ---------------------------------------------------------------------------

def target_to_words(target: int) -> np.ndarray:
    """256-bit target integer → msb-first u32 words, comparable against
    :func:`hash_words_be` output with :func:`lex_le`."""
    if not 0 <= target < 1 << 256:
        raise ValueError("target out of range")
    raw = target.to_bytes(32, "big")
    return np.frombuffer(raw, dtype=">u4").astype(np.uint32)


def digest_to_int(digest_words: np.ndarray) -> int:
    """(8,) digest words → Bitcoin's little-endian uint256 hash value
    (≡ ``chain.hash_to_int(digest_bytes)``)."""
    raw = b"".join(struct.pack(">I", int(w)) for w in digest_words)
    return int.from_bytes(raw, "little")
