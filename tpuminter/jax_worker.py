"""JaxMiner: the device-backed Worker (SURVEY.md §7 stage 3).

Satisfies the same ``worker.Miner`` generator contract as ``CpuMiner`` —
the BASELINE.json:5 requirement that accelerated backends slot into the
existing Miner/Worker interface — but runs each batch of nonces through
the jnp SHA-256 ops (``tpuminter.ops``) under ``jit``. On the CPU backend
this is the CI-testable stand-in; on TPU the same code drives the chip,
and the Pallas kernels (``tpuminter.kernels``) swap in underneath via the
``step_impl`` seam without touching the role layer.

Batching discipline (XLA semantics): every batch has the SAME static
shape — the final ragged batch is padded by clamping nonces to ``upper``
(duplicate nonces cannot change a min fold, and any padded winner still
names a valid in-range nonce) — so each (template, batch) pair compiles
exactly once.
"""

from __future__ import annotations

from functools import partial
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpuminter import chain
from tpuminter.ops import scrypt as scrypt_ops
from tpuminter.ops import sha256 as ops
from tpuminter.protocol import PowMode, Request, Result
from tpuminter.search import pipeline_spans, pull
from tpuminter.spans import DISPATCH, RESOLVE, span
from tpuminter.worker import Miner

__all__ = ["JaxMiner"]


@partial(jax.jit, static_argnums=0)
def _min_step(
    template: ops.NonceTemplate, nonce_hi: jnp.ndarray, nonce_lo: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Toy dialect: batch → (argmin index, its (hi, lo) u32 fold pair)."""
    digests = ops.sha256_batch(template, nonce_hi, nonce_lo)
    fold = digests[:, :2]  # toy_hash = first 8 digest bytes, big-endian
    idx = ops.lex_argmin(fold)
    return idx, fold[idx]


@partial(jax.jit, static_argnums=0)
def _target_step(
    template: ops.NonceTemplate, nonces: jnp.ndarray, target_words: jnp.ndarray
):
    """Bitcoin dialect: batch → (any_found, first_found_idx, min_idx,
    min_digest_words, first_found_digest_words)."""
    digests = ops.double_sha256_header_batch(template, nonces)
    hw = ops.hash_words_be(digests)
    ok = ops.lex_le(hw, target_words)
    found = ok.any()
    first = jnp.argmax(ok)  # 0 when none found; guarded by `found`
    midx = ops.lex_argmin(hw)
    return found, first, midx, digests[midx], digests[first]


@partial(jax.jit, static_argnums=3)
def _scrypt_step(
    header76w: jnp.ndarray, nonces: jnp.ndarray, target_words: jnp.ndarray,
    n_log2: int = 10,
):
    """Scrypt dialect (BASELINE.json:11): same contract as
    :func:`_target_step` with RFC 7914 scrypt as the PoW hash. The
    header words are a *runtime* input (scrypt admits no midstate
    specialization — the nonce sits in the PBKDF2 key), so one compile
    serves every job and every extranonce."""
    digests = scrypt_ops.scrypt_header_batch(header76w, nonces, n_log2)
    hw = ops.hash_words_be(digests)
    ok = ops.lex_le(hw, target_words)
    found = ok.any()
    first = jnp.argmax(ok)
    midx = ops.lex_argmin(hw)
    return found, first, midx, digests[midx], digests[first]


class JaxMiner(Miner):
    """Batched device miner behind the standard Worker interface."""

    backend = "jax"

    def __init__(
        self,
        batch: int = 1 << 16,
        lanes: Optional[int] = None,
        scrypt_batch: int = 256,
        depth: int = 2,
        roll_batch: int = 8,
    ):
        self.batch = batch
        #: extranonce rows per rolled dispatch (tpuminter.rolled): one
        #: batched roll + one batched sweep per `roll_batch` segments'
        #: worth of indices, pipelined across segment boundaries
        self.roll_batch = roll_batch
        # scrypt's ROMix scratch is 128 KiB per in-flight nonce, so the
        # memory-hard dialect gets its own (much smaller) batch size:
        # scrypt_batch × 128 KiB of V lives on device per step
        self.scrypt_batch = scrypt_batch
        # device calls kept in flight by the pipelined loops (scrypt):
        # the memory cost of depth 2 is one extra batch of V in flight
        self.depth = depth
        # scheduler hint: ask the coordinator for chunks a few batches deep
        self.lanes = lanes if lanes is not None else max(1, (batch * 4) // 16_384)

    # -- Miner interface -------------------------------------------------

    def mine(self, request: Request) -> Iterator[Optional[Result]]:
        if request.mode == PowMode.MIN:
            yield from self._mine_min(request)
        elif request.mode == PowMode.SCRYPT:
            yield from self._mine_scrypt(request)
        elif request.rolled:
            yield from self._mine_rolled(request)
        else:
            yield from self._mine_target(request)

    # -- internals -------------------------------------------------------

    def _batches(self, lower: int, upper: int, batch: Optional[int] = None):
        """Fixed-shape nonce batches covering [lower, upper], final batch
        padded with ``upper``; yields (start, valid_count, np_u64_array).

        The pad is built explicitly (not by clamping a full arange) so a
        range ending near 2^64 cannot wrap modulo 64 bits and leak
        out-of-range nonces into the batch.
        """
        batch = self.batch if batch is None else batch
        start = lower
        while start <= upper:
            valid = min(batch, upper - start + 1)
            nonces = np.uint64(start) + np.arange(valid, dtype=np.uint64)
            if valid < batch:
                nonces = np.concatenate(
                    [nonces, np.full(batch - valid, upper, dtype=np.uint64)]
                )
            yield start, valid, nonces
            start += valid

    def _mine_min(self, req: Request) -> Iterator[Optional[Result]]:
        template = ops.toy_template(req.data)
        best: Optional[Tuple[int, int]] = None  # (hash, nonce)
        for start, valid, nonces in self._batches(req.lower, req.upper):
            hi = jnp.asarray((nonces >> np.uint64(32)).astype(np.uint32))
            lo = jnp.asarray((nonces & np.uint64(0xFFFFFFFF)).astype(np.uint32))
            idx, fold = _min_step(template, hi, lo)
            idx = int(idx)
            h = (int(fold[0]) << 32) | int(fold[1])
            cand = (h, int(nonces[idx]))
            if best is None or cand < best:
                best = cand
            yield None
        yield Result(
            req.job_id, req.mode, best[1], best[0], found=True,
            searched=req.upper - req.lower + 1, chunk_id=req.chunk_id,
        )

    def _mine_target(self, req: Request) -> Iterator[Optional[Result]]:
        assert req.header is not None and req.target is not None
        template = ops.header_template(req.header)
        target_words = jnp.asarray(ops.target_to_words(req.target))
        best: Optional[Tuple[int, int]] = None  # (hash, nonce)
        for start, valid, nonces in self._batches(req.lower, req.upper):
            batch = jnp.asarray(nonces.astype(np.uint32))
            with span(DISPATCH):
                found, first, midx, min_digest, first_digest = _target_step(
                    template, batch, target_words
                )
            with span(RESOLVE):
                found = bool(found)
            if found:
                first = int(first)
                nonce = int(nonces[first])
                h = ops.digest_to_int(np.asarray(first_digest))
                yield Result(
                    req.job_id, req.mode, nonce, h, found=True,
                    searched=min(first + 1, valid) + (start - req.lower),
                    chunk_id=req.chunk_id,
                )
                return
            midx = int(midx)
            cand = (ops.digest_to_int(np.asarray(min_digest)), int(nonces[midx]))
            if best is None or cand < best:
                best = cand
            yield None
        yield Result(
            req.job_id, req.mode, best[1], best[0],
            found=best[0] <= req.target,
            searched=req.upper - req.lower + 1, chunk_id=req.chunk_id,
        )

    def _scrypt_segments(self, req: Request):
        """Yield ``(header76_bytes, global_base, lo, hi)`` per constant-
        header span of the request: the whole range for a plain job, one
        span per extranonce for a rolled one. The roll itself (coinbase →
        merkle root → header) happens on the HOST here: at scrypt's
        MH/s-scale rates one roll per 2^nonce_bits hashes is noise, so
        the on-device roll machinery (``ops.merkle``) is reserved for the
        GH/s double-SHA path where it matters."""
        if not req.rolled:
            yield req.header[:76], 0, req.lower, req.upper
            return
        cb = chain.CoinbaseTemplate(
            req.coinbase_prefix, req.coinbase_suffix, req.extranonce_size
        )
        for en, base_g, n_lo, n_hi in chain.rolled_segments(
            req.lower, req.upper, req.nonce_bits
        ):
            hdr76 = chain.rolled_header(req.header, cb, req.branch, en).pack()[:76]
            yield hdr76, base_g, n_lo, n_hi

    def _mine_scrypt(self, req: Request) -> Iterator[Optional[Result]]:
        """Memory-hard dialect (BASELINE.json:11): batched scrypt with
        the header words as runtime inputs — one compile total. Batches
        are double-buffered ``depth`` deep across segment boundaries
        (``search.pipeline_spans`` — VERDICT r5 weak #2: the per-batch
        ``bool(found)`` sync serialized the dispatch round trip with the
        ~1 s device step). Batches resolve in order, so the early exit's
        first-winner semantics are unchanged; a winner just leaves up to
        ``depth - 1`` in-flight batches unresolved (free for JAX async
        arrays)."""
        assert req.target is not None
        target_words = jnp.asarray(ops.target_to_words(req.target))

        def spans():
            for hdr76, base_g, lo, hi in self._scrypt_segments(req):
                hw = jnp.asarray(scrypt_ops.header_to_words(hdr76))
                for _, valid, nonces in self._batches(lo, hi, self.scrypt_batch):
                    yield hw, base_g, valid, nonces

        def dispatch(span):
            hw, _, _, nonces = span
            u32 = jnp.asarray(nonces.astype(np.uint32))
            found, first, midx, min_digest, first_digest = _scrypt_step(
                hw, u32, target_words
            )
            # one device array per batch (cf. search.pack_handle):
            # [found, first, midx, min_digest×8, first_digest×8]
            return jnp.concatenate([
                jnp.stack([
                    found.astype(jnp.uint32),
                    first.astype(jnp.uint32),
                    midx.astype(jnp.uint32),
                ]),
                min_digest, first_digest,
            ])

        best: Optional[Tuple[int, int]] = None  # (hash, global index)
        searched = 0
        for (_, base_g, valid, nonces), handle in pipeline_spans(
            spans(), dispatch, depth=self.depth
        ):
            row = pull(handle)
            if int(row[0]):
                first = int(row[1])
                g = base_g | int(nonces[first])
                h = ops.digest_to_int(row[11:19])
                yield Result(
                    req.job_id, req.mode, g, h, found=True,
                    searched=searched + min(first + 1, valid),
                    chunk_id=req.chunk_id,
                )
                return
            cand = (
                ops.digest_to_int(row[3:11]),
                base_g | int(nonces[int(row[2])]),
            )
            if best is None or cand < best:
                best = cand
            searched += valid
            yield None
        yield Result(
            req.job_id, req.mode, best[1], best[0],
            found=best[0] <= req.target,
            searched=searched, chunk_id=req.chunk_id,
        )

    def _mine_rolled(self, req: Request) -> Iterator[Optional[Result]]:
        """Extranonce-rolling TARGET search: the roll (coinbase txid →
        branch fold → merkle root → header midstate) runs ON DEVICE and
        its outputs feed the dynamic-header batch step without ever
        surfacing to the host (BASELINE.json:9-10). The BATCHED sweep
        (``tpuminter.rolled.mine_rolled_tracking``) — one roll + one
        sweep dispatch per ``roll_batch`` rows, pipelined ``depth``
        deep ACROSS segment boundaries."""
        from tpuminter import rolled

        yield from rolled.mine_rolled_tracking(
            req, width_cap=self.batch, depth=self.depth,
            roll_batch=self.roll_batch, progress=self.progress_cb,
        )
