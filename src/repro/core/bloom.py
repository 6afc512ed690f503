"""Multistage counting Bloom filter (paper §3.3.2, Fig. 8).

BFC communicates the set of paused flows upstream as a small idempotent
multistage Bloom filter. The switch keeps a *counting* filter per ingress so a
resume only clears a bit once no other paused flow maps to it.

The filter is represented as dense integer arrays so that thousands of filters
(one per link) update in parallel inside a jit-compiled step:

  counts : (..., n_stages, stage_bits) int32   -- counting filter at the switch
  bits   : (..., n_stages, stage_bits) bool    -- snapshot shipped upstream

A flow matches iff its bit is set in *every* stage. With 4 stages x 256 bits
(128 B total) and <=32 paused flows per ingress, the false-positive rate is
(32/256)^4 ~= 2.4e-4 per lookup, matching the paper's "1 in 5 million" for the
typical <=8 paused flows case.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .hashing import bloom_positions


@dataclass(frozen=True)
class BloomParams:
    n_stages: int = 4
    stage_bits: int = 256  # 4 stages x 256 bits = 128 B, paper's default

    @property
    def size_bytes(self) -> int:
        return self.n_stages * self.stage_bits // 8


def empty_counts(params: BloomParams, *lead_shape: int) -> jnp.ndarray:
    return jnp.zeros(lead_shape + (params.n_stages, params.stage_bits), jnp.int32)


def positions(fid: jnp.ndarray, params: BloomParams) -> jnp.ndarray:
    """Bit positions per stage: shape fid.shape + (n_stages,)."""
    return bloom_positions(fid, params.n_stages, params.stage_bits)


def add(counts: jnp.ndarray, pos: jnp.ndarray, enable) -> jnp.ndarray:
    """Increment the counters of one FID. ``pos``: (n_stages,), ``enable``: bool scalar.

    counts: (n_stages, stage_bits).
    """
    stage = jnp.arange(counts.shape[-2])
    return counts.at[stage, pos].add(jnp.where(enable, 1, 0))


def remove(counts: jnp.ndarray, pos: jnp.ndarray, enable) -> jnp.ndarray:
    """Decrement the counters of one FID (resume path, Fig. 8)."""
    stage = jnp.arange(counts.shape[-2])
    return counts.at[stage, pos].add(jnp.where(enable, -1, 0))


def add_batch(counts: jnp.ndarray, filt: jnp.ndarray, pos: jnp.ndarray,
              delta: jnp.ndarray) -> jnp.ndarray:
    """Batched counter update across many filters at once.

    counts : (n_filters, n_stages, stage_bits)
    filt   : (n,) int32 filter index per event (invalid events may use index 0
             with delta 0)
    pos    : (n, n_stages) bit positions
    delta  : (n,) int32 (+1 pause, -1 resume, 0 no-op)
    """
    n_stages = counts.shape[-2]
    stage = jnp.broadcast_to(jnp.arange(n_stages), pos.shape)
    f = jnp.broadcast_to(filt[:, None], pos.shape)
    d = jnp.broadcast_to(delta[:, None], pos.shape)
    return counts.at[f, stage, pos].add(d)


def snapshot(counts: jnp.ndarray) -> jnp.ndarray:
    """The bit filter actually shipped on the wire: counter > 0."""
    return counts > 0


def _pack(bits: jnp.ndarray) -> jnp.ndarray:
    """Bit filter as words: (..., stage_bits) bool -> (..., ceil(stage_bits /
    32)) uint32, bit ``i`` of a stage in bit ``i % 32`` of word ``i // 32``
    (the last word's bits past stage_bits are clear)."""
    pad = -bits.shape[-1] % 32
    if pad:
        bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    w = bits.reshape(bits.shape[:-1] + (bits.shape[-1] // 32, 32))
    return jnp.sum(w.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32),
                   axis=-1, dtype=jnp.uint32)


def check(bits: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Membership test of FIDs against snapshot(s).

    bits : (..., n_stages, stage_bits) bool
    pos  : (..., n_stages) int32, broadcast-compatible leading dims
    Returns bool array of the broadcast leading shape. True = paused (possibly
    a false positive; never a false negative).

    Dense, with no gather: each stage row is packed into 32-bit words, the
    word ``pos >> 5`` is picked by a compare over the row's words, and bit
    ``pos & 31`` is shifted out of it. A TPU prices a gather per index, so
    at 256 bits a stage a compare over 8 words costs less than one element
    read.
    """
    words = _pack(bits)                                  # (..., S, W)
    pick = (pos >> 5)[..., None] == jnp.arange(words.shape[-1])
    word = jnp.sum(jnp.where(pick, words, 0), axis=-1, dtype=jnp.uint32)
    got = (word >> (pos & 31).astype(jnp.uint32)) & 1
    return jnp.all(got != 0, axis=-1)
