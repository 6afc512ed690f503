"""Integer hashing utilities shared by the Bloom filter, flow table and ECMP.

All hashes are pure functions of a 32-bit flow identifier (FID) so they can be
precomputed per flow and used inside jit-compiled simulator steps.
"""
from __future__ import annotations

import jax.numpy as jnp

# Distinct odd multipliers (Knuth / splitmix-style avalanche constants).
_MULTS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1, 0x61C88647)


def _avalanche(x: jnp.ndarray) -> jnp.ndarray:
    """xorshift-multiply avalanche on uint32."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def hash_u32(x: jnp.ndarray, seed: int) -> jnp.ndarray:
    """Seeded 32-bit hash. ``x`` int32/uint32 array -> uint32 array."""
    x = x.astype(jnp.uint32) * jnp.uint32(_MULTS[seed % len(_MULTS)])
    x = x + jnp.uint32(seed * 0x01000193 + 0x811C9DC5)
    return _avalanche(x)


def bloom_positions(fid: jnp.ndarray, n_stages: int, stage_bits: int) -> jnp.ndarray:
    """Per-stage bit positions of ``fid`` in a multistage Bloom filter.

    Returns shape fid.shape + (n_stages,), values in [0, stage_bits).
    """
    pos = [hash_u32(fid, s) % jnp.uint32(stage_bits) for s in range(n_stages)]
    return jnp.stack(pos, axis=-1).astype(jnp.int32)


def bucket_index(fid: jnp.ndarray, n_buckets: int) -> jnp.ndarray:
    """Flow-table bucket for a FID (hash table with 4-entry buckets, §3.3.3)."""
    return (hash_u32(fid, 4) % jnp.uint32(n_buckets)).astype(jnp.int32)


# hash seeds of the ECMP choice at each tier a path climbs (seeds 0-3 are
# the Bloom stages, 4 the flow-table bucket)
ECMP_SEEDS = (5, 7)


def ecmp_choice(fid: jnp.ndarray, n_paths: int,
                level: int = 0) -> jnp.ndarray:
    """Flow-level ECMP: consistent uplink choice per flow at tier `level`
    (0: the first uplink, the leaf-spine's spine; 1: the next tier up),
    by independent hashes of the flow id."""
    return (hash_u32(fid, ECMP_SEEDS[level]) % jnp.uint32(n_paths)
            ).astype(jnp.int32)
