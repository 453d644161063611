"""Chain primitives: block headers, difficulty targets, Merkle trees,
coinbase / extraNonce rolling, and host-side hashing.

Capability parity notes (reference mount empty — SURVEY.md §0; expected
reference paths from SURVEY.md §2):

- ``toy_hash`` ≙ reference ``bitcoin/hash.go`` ``Hash(message, nonce)``:
  the reference's toy proof-of-work is "find the nonce *minimizing* a
  uint64 fold of SHA-256(message ‖ nonce)". The exact fold/encoding is a
  student-era free choice (SURVEY.md §0 [U]); we define it as the first
  8 bytes (big-endian) of SHA-256(data ‖ nonce_be8).
- Everything else here (80-byte headers, bits→target, double-SHA-256,
  Merkle, extraNonce) is the *capability delta* demanded by
  BASELINE.json:6-12 beyond the reference: real Bitcoin semantics.

All functions are pure, host-side (hashlib / pure Python). Device-side
equivalents live in ``tpuminter.ops`` / ``tpuminter.kernels``.
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass, replace
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

__all__ = [
    "sha256",
    "dsha256",
    "scrypt_hash",
    "sha256_compress",
    "midstate",
    "bits_to_target",
    "target_to_bits",
    "hash_to_int",
    "hash_to_hex",
    "toy_hash",
    "BlockHeader",
    "GENESIS_HEADER",
    "GENESIS_HASH_HEX",
    "merkle_root",
    "merkle_branch",
    "merkle_root_from_branch",
    "CoinbaseTemplate",
    "rolled_header",
    "split_global",
    "roll_span",
    "rolled_segments",
    "rolled_tiles",
    "HEADER_SIZE",
    "SHA256_H0",
    "SHA256_K",
]

HEADER_SIZE = 80

# ---------------------------------------------------------------------------
# SHA-256 (host side)
# ---------------------------------------------------------------------------

#: SHA-256 round constants (FIPS 180-4 §4.2.2).
SHA256_K: Tuple[int, ...] = (
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5,
    0x3956C25B, 0x59F111F1, 0x923F82A4, 0xAB1C5ED5,
    0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174,
    0xE49B69C1, 0xEFBE4786, 0x0FC19DC6, 0x240CA1CC,
    0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7,
    0xC6E00BF3, 0xD5A79147, 0x06CA6351, 0x14292967,
    0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85,
    0xA2BFE8A1, 0xA81A664B, 0xC24B8B70, 0xC76C51A3,
    0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5,
    0x391C0CB3, 0x4ED8AA4A, 0x5B9CCA4F, 0x682E6FF3,
    0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
)

#: SHA-256 initial hash state (FIPS 180-4 §5.3.3).
SHA256_H0: Tuple[int, ...] = (
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
)

_MASK32 = 0xFFFFFFFF


def sha256(data: bytes) -> bytes:
    """Single SHA-256 digest (hashlib-backed)."""
    return hashlib.sha256(data).digest()


def dsha256(data: bytes) -> bytes:
    """Bitcoin's double SHA-256: SHA-256(SHA-256(data))."""
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


def scrypt_hash(data: bytes, n: int = 1024) -> bytes:
    """Litecoin-style scrypt PoW hash: ``scrypt(P=data, S=data, N=n,
    r=1, p=1, dkLen=32)`` (RFC 7914 via OpenSSL; BASELINE.json:11).
    ``data`` is the 80-byte header; the 32-byte output is interpreted
    exactly like a double-SHA digest (``hash_to_int`` little-endian
    value vs target). Host ground truth for ``ops.scrypt``."""
    return hashlib.scrypt(data, salt=data, n=n, r=1, p=1, dklen=32)


def _rotr(x: int, n: int) -> int:
    return ((x >> n) | (x << (32 - n))) & _MASK32


def sha256_compress(state: Sequence[int], block: bytes) -> Tuple[int, ...]:
    """One SHA-256 compression round over a 64-byte block.

    Pure-Python reference implementation. Exists because hashlib does not
    expose the intermediate state ("midstate") after each block, and the
    midstate of the first 64 header bytes is the key specialization the
    device kernels rely on: only the last 16 header bytes vary per *work
    unit*, and of those only the 4 nonce bytes vary per *candidate*.
    """
    if len(block) != 64:
        raise ValueError(f"sha256_compress needs a 64-byte block, got {len(block)}")
    w = list(struct.unpack(">16I", block))
    for i in range(16, 64):
        s0 = _rotr(w[i - 15], 7) ^ _rotr(w[i - 15], 18) ^ (w[i - 15] >> 3)
        s1 = _rotr(w[i - 2], 17) ^ _rotr(w[i - 2], 19) ^ (w[i - 2] >> 10)
        w.append((w[i - 16] + s0 + w[i - 7] + s1) & _MASK32)
    a, b, c, d, e, f, g, h = state
    for i in range(64):
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = (h + s1 + ch + SHA256_K[i] + w[i]) & _MASK32
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = (s0 + maj) & _MASK32
        h, g, f, e, d, c, b, a = g, f, e, (d + t1) & _MASK32, c, b, a, (t1 + t2) & _MASK32
    return tuple((s + v) & _MASK32 for s, v in zip(state, (a, b, c, d, e, f, g, h)))


def midstate(header_prefix64: bytes) -> Tuple[int, ...]:
    """SHA-256 state after compressing the first 64 bytes of a header.

    The mining hot path hashes ``header ‖ padding`` where only the final
    16 header bytes (merkle tail, time, bits, nonce) vary per candidate;
    the midstate over bytes [0, 64) is computed once per work unit and
    shipped to every worker / device lane.
    """
    if len(header_prefix64) != 64:
        raise ValueError("midstate needs exactly the first 64 header bytes")
    return sha256_compress(SHA256_H0, header_prefix64)


# ---------------------------------------------------------------------------
# Difficulty encoding
# ---------------------------------------------------------------------------

def bits_to_target(bits: int) -> int:
    """Decode Bitcoin 'compact bits' difficulty encoding to a 256-bit target.

    target = mantissa * 256^(exponent-3), bits = (exponent << 24) | mantissa.
    """
    exponent = bits >> 24
    mantissa = bits & 0x007FFFFF
    if bits & 0x00800000:
        raise ValueError("negative target in compact bits encoding")
    if exponent <= 3:
        return mantissa >> (8 * (3 - exponent))
    return mantissa << (8 * (exponent - 3))


def target_to_bits(target: int) -> int:
    """Encode a 256-bit target back to compact bits (canonical form)."""
    if target <= 0:
        raise ValueError("target must be positive")
    size = (target.bit_length() + 7) // 8
    if size <= 3:
        mantissa = target << (8 * (3 - size))
    else:
        mantissa = target >> (8 * (size - 3))
    if mantissa & 0x00800000:  # would look negative; shift into the exponent
        mantissa >>= 8
        size += 1
    return (size << 24) | mantissa


def hash_to_int(digest32: bytes) -> int:
    """Interpret a 32-byte double-SHA digest as Bitcoin's little-endian uint256."""
    return int.from_bytes(digest32, "little")


def hash_to_hex(digest32: bytes) -> str:
    """Display form: the digest byte-reversed, hex encoded (as in explorers)."""
    return digest32[::-1].hex()


# ---------------------------------------------------------------------------
# Toy proof-of-work (reference parity mode)
# ---------------------------------------------------------------------------

def toy_hash(data: bytes, nonce: int) -> int:
    """uint64 fold of SHA-256(data ‖ nonce), minimized by the toy PoW mode.

    ≙ reference ``bitcoin/hash.go`` ``Hash``. Encoding choice (see module
    docstring): nonce appended as 8 bytes big-endian; fold = first 8
    digest bytes, big-endian.
    """
    digest = hashlib.sha256(data + struct.pack(">Q", nonce)).digest()
    return int.from_bytes(digest[:8], "big")


# ---------------------------------------------------------------------------
# Block header
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockHeader:
    """An 80-byte Bitcoin block header.

    ``prev_hash`` and ``merkle_root`` are stored in *internal* byte order
    (the order they are serialized in), i.e. the byte-reverse of the hex
    shown by block explorers.
    """

    version: int
    prev_hash: bytes
    merkle_root: bytes
    timestamp: int
    bits: int
    nonce: int

    def __post_init__(self) -> None:
        if len(self.prev_hash) != 32 or len(self.merkle_root) != 32:
            raise ValueError("prev_hash / merkle_root must be 32 bytes")

    def pack(self) -> bytes:
        return (
            struct.pack("<I", self.version)
            + self.prev_hash
            + self.merkle_root
            + struct.pack("<III", self.timestamp, self.bits, self.nonce & _MASK32)
        )

    @staticmethod
    def unpack(raw: bytes) -> "BlockHeader":
        if len(raw) != HEADER_SIZE:
            raise ValueError(f"header must be {HEADER_SIZE} bytes, got {len(raw)}")
        version = struct.unpack_from("<I", raw, 0)[0]
        prev_hash = raw[4:36]
        merkle_root = raw[36:68]
        timestamp, bits, nonce = struct.unpack_from("<III", raw, 68)
        return BlockHeader(version, prev_hash, merkle_root, timestamp, bits, nonce)

    def with_nonce(self, nonce: int) -> "BlockHeader":
        return replace(self, nonce=nonce & _MASK32)

    def with_merkle_root(self, root: bytes) -> "BlockHeader":
        return replace(self, merkle_root=root)

    def block_hash(self) -> bytes:
        return dsha256(self.pack())

    def block_hash_int(self) -> int:
        return hash_to_int(self.block_hash())

    def meets_target(self, target: int | None = None) -> bool:
        if target is None:
            target = bits_to_target(self.bits)
        return self.block_hash_int() <= target

    # -- device-kernel plumbing ------------------------------------------

    def midstate(self) -> Tuple[int, ...]:
        """SHA-256 state after the first 64 packed bytes (nonce-independent)."""
        return midstate(self.pack()[:64])

    def tail_words(self) -> Tuple[int, int, int]:
        """Big-endian u32 words 0-2 of the header's second SHA block.

        Word 3 is the (byte-swapped) nonce and is what the device kernels
        vary; words 4-15 are fixed SHA padding for an 80-byte message.
        """
        raw = self.pack()
        return struct.unpack(">3I", raw[64:76])


GENESIS_HEADER = BlockHeader(
    version=1,
    prev_hash=b"\x00" * 32,
    merkle_root=bytes.fromhex(
        "4a5e1e4baab89f3a32518a88c31bc87f618f76673e2cc77ab2127b7afdeda33b"
    )[::-1],
    timestamp=1231006505,
    bits=0x1D00FFFF,
    nonce=2083236893,
)

GENESIS_HASH_HEX = "000000000019d6689c085ae165831e934ff763ae46a2a6c172b3f1b60a8ce26f"


# ---------------------------------------------------------------------------
# Merkle trees
# ---------------------------------------------------------------------------

def merkle_root(txids: Sequence[bytes]) -> bytes:
    """Bitcoin Merkle root over txids (internal byte order).

    Odd levels duplicate their last element, per consensus rules.
    """
    if not txids:
        raise ValueError("merkle_root needs at least one txid")
    level: List[bytes] = list(txids)
    while len(level) > 1:
        if len(level) % 2:
            level.append(level[-1])
        level = [dsha256(level[i] + level[i + 1]) for i in range(0, len(level), 2)]
    return level[0]


def merkle_branch(txids: Sequence[bytes], index: int = 0) -> List[bytes]:
    """Sibling-hash path for leaf ``index`` (stratum-style, default: coinbase).

    Combined with :func:`merkle_root_from_branch`, lets the root be
    recomputed from just the (mutated) leaf — the mechanism behind
    extraNonce rolling, on host and on device alike.
    """
    if not txids:
        raise ValueError("merkle_branch needs at least one txid")
    branch: List[bytes] = []
    level: List[bytes] = list(txids)
    idx = index
    while len(level) > 1:
        if len(level) % 2:
            level.append(level[-1])
        sibling = idx ^ 1
        branch.append(level[sibling])
        level = [dsha256(level[i] + level[i + 1]) for i in range(0, len(level), 2)]
        idx //= 2
    return branch


def merkle_root_from_branch(leaf: bytes, branch: Iterable[bytes], index: int = 0) -> bytes:
    """Fold a leaf up a Merkle branch to the root."""
    node = leaf
    idx = index
    for sibling in branch:
        if idx & 1:
            node = dsha256(sibling + node)
        else:
            node = dsha256(node + sibling)
        idx //= 2
    return node


# ---------------------------------------------------------------------------
# Coinbase / extraNonce
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CoinbaseTemplate:
    """A coinbase transaction split around its extraNonce bytes.

    ``txid(extranonce) = dsha256(prefix ‖ extranonce_leN ‖ suffix)`` — the
    stratum-style shape that makes extraNonce rolling a pure function of an
    integer, so it can run on device (BASELINE.json:9-10). When the 32-bit
    header nonce space exhausts, bump extranonce, recompute the coinbase
    txid, fold it up ``branch`` to a fresh merkle root, and restart.
    """

    prefix: bytes
    suffix: bytes
    extranonce_size: int = 4

    def serialize(self, extranonce: int) -> bytes:
        return (
            self.prefix
            + int(extranonce).to_bytes(self.extranonce_size, "little")
            + self.suffix
        )

    def txid(self, extranonce: int) -> bytes:
        return dsha256(self.serialize(extranonce))

    def merkle_root(self, extranonce: int, branch: Sequence[bytes]) -> bytes:
        return merkle_root_from_branch(self.txid(extranonce), branch, index=0)


def rolled_header(
    header80: bytes,
    coinbase: CoinbaseTemplate,
    branch: Sequence[bytes],
    extranonce: int,
) -> BlockHeader:
    """The header actually mined at a given extranonce: ``header80``'s
    merkle-root field replaced by the root recomputed from the mutated
    coinbase (BASELINE.json:9-10's roll, host reference semantics; the
    device equivalent is ``tpuminter.ops.merkle.extranonce_roll``,
    one row per extranonce).
    """
    root = coinbase.merkle_root(extranonce, branch)
    return BlockHeader.unpack(header80).with_merkle_root(root)


def split_global(index: int, nonce_bits: int = 32) -> Tuple[int, int]:
    """A rolled job's global search index → ``(extranonce, nonce)``.

    The search space is the product (extranonce × nonce): global index
    ``g`` means extranonce ``g >> nonce_bits`` with header nonce
    ``g & (2^nonce_bits - 1)``. ``nonce_bits`` is 32 in production (the
    header nonce field is u32); tests shrink it so a roll happens within
    a tractable sweep.
    """
    return index >> nonce_bits, index & ((1 << nonce_bits) - 1)


def roll_span(
    extranonce0: int, count: int, nonce_bits: int = 32
) -> Tuple[int, int]:
    """Inclusive global-index range a roll-budget assign covers: ``count``
    whole extranonce segments starting at ``extranonce0``, each spanning
    the full ``2^nonce_bits`` header-nonce space. The single source of
    the RollAssign → ``[lower, upper]`` expansion — coordinator carving
    and worker expansion must agree on it bit-for-bit, or the exactly-
    once range ledger double-counts."""
    if count < 1:
        raise ValueError("roll_span needs count >= 1")
    lower = extranonce0 << nonce_bits
    return lower, ((extranonce0 + count) << nonce_bits) - 1


def rolled_segments(
    lower: int, upper: int, nonce_bits: int = 32
) -> Iterator[Tuple[int, int, int, int]]:
    """Split a rolled job's global-index range ``[lower, upper]`` into
    per-extranonce segments ``(extranonce, global_base, nonce_lo,
    nonce_hi)`` — the spans over which the header is constant. Inverse
    bookkeeping of :func:`split_global`; every rolled miner iterates
    this (the single source of the en/segment arithmetic)."""
    idx = lower
    mask = (1 << nonce_bits) - 1
    while idx <= upper:
        en = idx >> nonce_bits
        seg_end = min(upper, ((en + 1) << nonce_bits) - 1)
        yield en, en << nonce_bits, idx & mask, seg_end & mask
        idx = seg_end + 1


def rolled_tiles(
    lower: int, upper: int, nonce_bits: int = 32, width: Optional[int] = None
) -> Iterator[Tuple[int, int, int, int]]:
    """:func:`rolled_segments` sub-split at ``width`` granularity: yield
    ``(extranonce, nonce_base, count, global_base)`` tiles, each at most
    ``width`` nonces wide and never crossing an extranonce boundary — the
    unit of work one ROW of a batched rolled sweep covers
    (``tpuminter.rolled``). Tiles come out in ascending global order;
    ``global_base`` is the global index of the tile's first nonce.
    ``width=None`` means whole segments (≡ ``rolled_segments`` reshaped).
    """
    for en, base_g, n_lo, n_hi in rolled_segments(lower, upper, nonce_bits):
        if width is None or width >= (1 << nonce_bits):
            yield en, n_lo, n_hi - n_lo + 1, base_g | n_lo
            continue
        b = n_lo
        while b <= n_hi:
            take = min(width, n_hi - b + 1)
            yield en, b, take, base_g | b
            b += take
