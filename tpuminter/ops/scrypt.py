"""Scrypt (RFC 7914) on device: the memory-hard PoW variant
(BASELINE.json:11, eval config 5; SURVEY.md §7 stage 7).

Litecoin-style header mining: ``scrypt(P=header80, S=header80, N=1024,
r=1, p=1, dkLen=32)``, the 32-byte output interpreted as a little-endian
uint256 and compared against the target exactly like Bitcoin's
double-SHA hash value. The reference has no scrypt (its toy PoW is a
folded single SHA); host ground truth is OpenSSL via
``chain.scrypt_hash`` / ``hashlib.scrypt``, which the batch function
here is pinned against bit-for-bit (tests/test_scrypt.py).

TPU-first design notes:

- **Everything is u32 vector ALU + one gather.** Salsa20/8 and the
  SHA-256 compressions are elementwise over the batch, so XLA tiles
  them onto the VPU like the SHA ops. The one irreducibly memory-hard
  step is ROMix phase 2's data-dependent read ``V[Integerify(X)]`` —
  that is scrypt's *point* (sequential memory hardness), and it lowers
  to a per-lane dynamic-slice/gather from the ``N × 128``-byte scratch
  ``V`` that XLA keeps in HBM. Throughput is therefore HBM-bandwidth
  bound by design: each hash writes and reads 128 KiB at N=1024/r=1.
- **No midstate tricks apply.** Unlike double-SHA mining, the nonce
  sits in the PBKDF2 *key* (P = the header itself), so every SHA state
  depends on the nonce from the first block; the whole pipeline is
  recomputed per nonce. Consequently the header travels as a *runtime*
  (19,) u32 array — nothing job-specific is baked, one compiled
  program serves every header-mining job and every extranonce.
- **Static shapes, static N.** ``n_log2`` is a static arg; both ROMix
  phases are ``lax.scan``s over tuples of per-word ``(B,)`` vectors
  (see :func:`romix` for the measured layout rationale). Batch size
  fixes the compile; memory is ``batch × 128·N`` bytes for V (32 MiB
  at batch=256, N=1024; 2 GiB at the TPU batch of 16384).

Word-order convention: SHA-256 words are big-endian reads of the byte
stream (as in ``ops.sha256``); salsa/BlockMix words are little-endian
(RFC 7914 §3). ``_bswap`` converts at the two seams (B after the first
PBKDF2, B' before the last).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpuminter.chain import SHA256_H0
from tpuminter.ops import sha256 as ops

__all__ = [
    "salsa20_8",
    "block_mix",
    "romix",
    "scrypt_header_batch",
    "HEADER_WORDS",
]

_H0 = np.array(SHA256_H0, dtype=np.uint32)
#: words of the 76-byte constant header prefix (the nonce completes it)
HEADER_WORDS = 19

#: outer-HMAC second block: 0x80 pad + bit length of opad(64) ‖ digest(32)
_OUTER_PAD = np.array([0x80000000, 0, 0, 0, 0, 0, 0, 768], dtype=np.uint32)


_bswap = ops.byteswap32  # the BE↔LE word seam (shared helper)

def _compress(state, block):
    # scanned rounds, never unrolled: the PBKDF2 walls embed 21
    # compressions in one program, and 21 × ~7k unrolled ops push XLA
    # compile time into minutes for ~2% of scrypt's runtime
    return ops.compress(state, block, unroll=False)



def _rotl(x: jnp.ndarray, n: int) -> jnp.ndarray:
    # no rotate ISA on TPU: shift/or pair, same as the SHA ops
    return (x << np.uint32(n)) | (x >> np.uint32(32 - n))


#: salsa20 quarter-round index pattern: (target, a, b, rot) meaning
#: ``x[target] ^= rotl(x[a] + x[b], rot)``; first the 4 column quarter-
#: rounds then the 4 row quarter-rounds = one double round (Salsa20 spec
#: §/RFC 7914 §2 reference code ordering).
_SALSA_STEPS: Tuple[Tuple[int, int, int, int], ...] = (
    # column round
    (4, 0, 12, 7), (8, 4, 0, 9), (12, 8, 4, 13), (0, 12, 8, 18),
    (9, 5, 1, 7), (13, 9, 5, 9), (1, 13, 9, 13), (5, 1, 13, 18),
    (14, 10, 6, 7), (2, 14, 10, 9), (6, 2, 14, 13), (10, 6, 2, 18),
    (3, 15, 11, 7), (7, 3, 15, 9), (11, 7, 3, 13), (15, 11, 7, 18),
    # row round
    (1, 0, 3, 7), (2, 1, 0, 9), (3, 2, 1, 13), (0, 3, 2, 18),
    (6, 5, 4, 7), (7, 6, 5, 9), (4, 7, 6, 13), (5, 4, 7, 18),
    (11, 10, 9, 7), (8, 11, 10, 9), (9, 8, 11, 13), (10, 9, 8, 18),
    (12, 15, 14, 7), (13, 12, 15, 9), (14, 13, 12, 13), (15, 14, 13, 18),
)


def _salsa20_8_words(w):
    """Salsa20/8 on 16 separate word vectors (the TPU-dense form: each
    word is a whole ``(B,)`` array, so every op is a full-vreg VPU op
    with no cross-lane extracts). Returns 16 new word vectors."""
    x = list(w)
    for _ in range(4):
        for tgt, a, b, rot in _SALSA_STEPS:
            x[tgt] = x[tgt] ^ _rotl(x[a] + x[b], rot)
    return [wi + xi for wi, xi in zip(w, x)]


def _block_mix_words(w32):
    """scryptBlockMix r=1 on 32 word vectors: ``Y0 = salsa(B1 ^ B0)``,
    ``Y1 = salsa(Y0 ^ B1)``, output ``Y0 ‖ Y1`` (RFC 7914 §4)."""
    b0, b1 = w32[:16], w32[16:]
    y0 = _salsa20_8_words([p ^ q for p, q in zip(b1, b0)])
    y1 = _salsa20_8_words([p ^ q for p, q in zip(y0, b1)])
    return y0 + y1


def salsa20_8(x: jnp.ndarray) -> jnp.ndarray:
    """Salsa20/8 core: ``(..., 16) u32`` little-endian words → same shape
    (RFC 7914 §2). 4 double rounds, then the feed-forward add."""
    return jnp.stack(
        _salsa20_8_words([x[..., i] for i in range(16)]), axis=-1
    )


def block_mix(x: jnp.ndarray) -> jnp.ndarray:
    """scryptBlockMix for r=1: ``(..., 32) u32`` LE words → same shape
    (RFC 7914 §4)."""
    return jnp.stack(
        _block_mix_words([x[..., i] for i in range(32)]), axis=-1
    )


@partial(jax.jit, static_argnums=1)
def romix(x: jnp.ndarray, n_log2: int) -> jnp.ndarray:
    """scryptROMix for r=1 (RFC 7914 §5), batched: ``(B, 32) u32`` LE
    words → same shape, with ``N = 2**n_log2``.

    Phase 1 (``lax.scan``) fills ``V[i] = BlockMix^i(X)``; phase 2 does
    the sequential data-dependent walk ``X = BlockMix(X ^
    V[Integerify(X) mod N])``. Integerify for r=1 = LE word 16 (first
    word of the last 64-byte block).

    Two TPU-measured layout choices carry the performance (each is
    ~100× over the naive form on a v5e):

    - **State lives as 32 separate ``(B,)`` word vectors**, not a
      ``(B, 32)`` array: on TPU the minor axis is the 128-lane dim, so
      ``x[:, i]`` word extracts inside salsa are strided cross-lane
      ops that dominate runtime; word-per-array makes every salsa op a
      dense full-vreg VPU op. The pack/unpack to ``(B, 32)`` happens
      once per step (V store / V load), not ~600× per BlockMix.
    - **V is flat ``(N·B, 32)`` and phase 2 gathers whole rows** via
      ``v[j·B + lane]``: XLA lowers this integer row-gather well
      (measured ~23 GB/s at B≥8192), while ``take_along_axis`` on the
      ``(N, B, 32)`` form lowers ~100× slower. Throughput remains
      HBM-gather bound — that is scrypt's design point (sequential
      memory hardness), and why a memory-hard PoW on any
      matmul-oriented part is bandwidth-, not ALU-, limited.
    """
    n = 1 << n_log2
    batch = x.shape[0]
    if n * batch >= 1 << 31:
        # the flat row index is computed in u32 and cast to int32; past
        # 2^31 rows it would wrap/clamp silently into wrong V reads
        raise ValueError(
            f"n*batch = {n * batch} exceeds the int32 row-index domain; "
            "shrink the batch or n_log2"
        )
    lane = jnp.arange(batch, dtype=jnp.uint32)
    words = tuple(x[:, i] for i in range(32))

    # unroll=2 on TPU: measured +11.5% at the shipping B=16384 (unroll=4
    # regresses); kept at 1 on the CPU mesh where CI would pay a doubled
    # scan-body compile for zero benefit (the knob only reschedules; the
    # math is identical). A fully-fused Pallas ROMix was prototyped and
    # rejected on measurement, and round 5 measured SIX fused
    # relayout+xor+salsa designs — pallas kernels on every byte layout
    # the gather can emit (incl. its native sublane-interleaved tiles),
    # a plane-major element gather, and an MXU identity-dot transpose —
    # all ~650 µs/step or worse on v5e: the walk is floor-bound by the
    # TPU gather emitter's custom-call/relayout boundary, not by this
    # scan body. See PERF.md's scrypt section.
    unroll = 2 if jax.default_backend() != "cpu" else 1

    def fill(carry, _):
        return tuple(_block_mix_words(list(carry))), jnp.stack(carry, axis=-1)

    words, v = jax.lax.scan(fill, words, None, length=n, unroll=unroll)
    vflat = v.reshape(n * batch, 32)  # v: (N, B, 32)

    def walk(carry, _):
        j = carry[16] & np.uint32(n - 1)  # (B,) per-lane index into V
        vj = vflat[(j * np.uint32(batch) + lane).astype(jnp.int32)]
        mixed = [c ^ vj[:, i] for i, c in enumerate(carry)]
        return tuple(_block_mix_words(mixed)), None

    words, _ = jax.lax.scan(walk, words, None, length=n, unroll=unroll)
    return jnp.stack(words, axis=-1)


# ---------------------------------------------------------------------------
# PBKDF2-HMAC-SHA256 pieces (c=1, the only iteration count scrypt uses)
# ---------------------------------------------------------------------------

def _hmac_states(key8: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """HMAC-SHA256 inner/outer states for a 32-byte key (here always
    SHA256(header) — header80 > 64 bytes forces the key-hash path):
    ``(..., 8) u32`` → two ``(..., 8)`` states after the ipad/opad
    blocks."""
    shape = key8.shape[:-1] + (8,)
    h0 = jnp.broadcast_to(jnp.asarray(_H0), shape)
    ipad = jnp.concatenate(
        [key8 ^ np.uint32(0x36363636),
         jnp.full(shape, 0x36363636, jnp.uint32)], axis=-1
    )
    opad = jnp.concatenate(
        [key8 ^ np.uint32(0x5C5C5C5C),
         jnp.full(shape, 0x5C5C5C5C, jnp.uint32)], axis=-1
    )
    return _compress(h0, ipad), _compress(h0, opad)


def _hmac_finish(ostate: jnp.ndarray, inner_digest: jnp.ndarray) -> jnp.ndarray:
    """Outer hash: opad state + 32-byte inner digest → (..., 8) u32."""
    pad = jnp.broadcast_to(jnp.asarray(_OUTER_PAD), inner_digest.shape)
    return _compress(ostate, jnp.concatenate([inner_digest, pad], axis=-1))


def _const_row(shape, words) -> jnp.ndarray:
    return jnp.broadcast_to(
        jnp.asarray(np.array(words, dtype=np.uint32)), shape[:-1] + (len(words),)
    )


@partial(jax.jit, static_argnums=(2, 3))
def scrypt_header_batch(
    header76w: jnp.ndarray,
    nonces: jnp.ndarray,
    n_log2: int = 10,
    romix_impl=romix,
) -> jnp.ndarray:
    """Scrypt PoW hashes for a batch of header nonces:
    ``header76w (19,) u32`` (big-endian words of the 76 constant header
    bytes — a *runtime* value, nothing baked) × ``nonces (B,) u32`` →
    ``(B, 8) u32`` big-endian words of the 32-byte scrypt output, the
    same digest-word convention as ``ops.sha256_batch`` (so
    ``hash_words_be`` / ``digest_to_int`` / ``lex_le`` apply unchanged).

    ≡ ``hashlib.scrypt(hdr, salt=hdr, n=2**n_log2, r=1, p=1, dklen=32)``
    with ``hdr = header76 ‖ nonce_le`` (pinned by tests/test_scrypt.py).
    ``romix_impl`` is the kernel seam: the default is the jnp ROMix; a
    Pallas ROMix slots in underneath without touching the PBKDF2 walls.
    """
    b = nonces.shape[0]
    hw = jnp.broadcast_to(header76w, (b, HEADER_WORDS))
    nw = _bswap(nonces)[:, None]  # LE nonce bytes as a BE schedule word
    block0 = hw[:, :16]
    tail3 = hw[:, 16:]

    # key = SHA256(header80): 80 bytes → block0 + (tail ‖ nonce ‖ pad)
    h0 = jnp.broadcast_to(jnp.asarray(_H0), (b, 8))
    key_tail = jnp.concatenate(
        [tail3, nw, _const_row((b, 16), [0x80000000] + [0] * 10 + [640])],
        axis=-1,
    )
    key8 = _compress(_compress(h0, block0), key_tail)
    istate, ostate = _hmac_states(key8)

    # B = PBKDF2(P=hdr, S=hdr, c=1, dkLen=128): 4 HMAC blocks, inner
    # message = S ‖ INT_BE(i). The S-block0 compression is i-independent.
    mid = _compress(istate, block0)
    t_be = []
    for i in (1, 2, 3, 4):
        inner_tail = jnp.concatenate(
            [tail3, nw,
             _const_row((b, 16), [i, 0x80000000] + [0] * 9 + [1184])],
            axis=-1,
        )
        t_be.append(_hmac_finish(ostate, _compress(mid, inner_tail)))
    x = _bswap(jnp.concatenate(t_be, axis=-1))  # (B, 32) LE words

    x = romix_impl(x, n_log2)

    # out = PBKDF2(P=hdr, S=B', c=1, dkLen=32): one HMAC block, inner
    # message = B'(128 bytes) ‖ INT_BE(1)
    bp = _bswap(x)  # B' bytes as BE schedule words
    st = _compress(_compress(istate, bp[:, :16]), bp[:, 16:])
    last = _const_row((b, 16), [1, 0x80000000] + [0] * 13 + [1568])
    return _hmac_finish(ostate, _compress(st, last))


def header_to_words(header_prefix76: bytes) -> np.ndarray:
    """76-byte header prefix → the (19,) u32 big-endian word array
    :func:`scrypt_header_batch` consumes."""
    if len(header_prefix76) != 76:
        raise ValueError(f"header prefix must be 76 bytes, got {len(header_prefix76)}")
    return np.frombuffer(header_prefix76, dtype=">u4").astype(np.uint32)
