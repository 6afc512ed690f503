"""Pure-jnp oracle for the BFC switch decision kernel — the same math
`repro.sim.engine` uses inline each tick."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

# Largest priority key a packed DRR/SRF entry may carry. SRF state keys are
# clamped here before packing (mirrors the engine's jnp.minimum(qsrf, BIG)).
BIG = 1 << 20


def packed_sentinel(nq: int, max_key: int) -> int:
    """Smallest packed value strictly above every real (key, queue) pair.

    Packed priorities are ``key * nq + q_ix`` with key <= max_key and
    q_ix < nq, so ``(max_key + 1) * nq`` can never collide with a real
    entry. (A fixed ``1 << 20`` sentinel used to stand here; it silently
    collided once ``key * nq + q_ix`` reached 2^20 — with large Q a real
    last-queue pick read as "no eligible queue".)"""
    sentinel = (max_key + 1) * nq
    assert sentinel <= np.iinfo(np.int32).max, (
        f"packed scheduler key overflows int32: nq={nq} max_key={max_key}")
    return sentinel


def pause_threshold(n_active, pause_window: int):
    """BFC's per-port pause threshold ceil(pause_window / n_active)
    (paper §3.3.2) in integer arithmetic; >= 1 since n_active >= 1 and
    pause_window >= 1. The one copy of this law: the Pallas kernel, this
    oracle and the engine's lax path all call it, so the two decision
    paths cannot round differently on any backend (a float32 quotient
    need not be correctly rounded on every chip)."""
    return (pause_window + n_active - 1) // n_active


def bfc_decide_ref(occ, qpaused, ptr, *, pause_window: int):
    p, q = occ.shape
    sentinel = packed_sentinel(q, q - 1)
    active = (occ > 0) & ~qpaused
    n_act = jnp.maximum(active.sum(axis=1), 1)
    th = pause_threshold(n_act, pause_window)
    pause = occ > th[:, None]
    q_ix = jnp.arange(q)[None, :]
    drr_key = (q_ix - ptr[:, None]) % q
    packed = jnp.where(active, drr_key * q + q_ix, sentinel)
    best = packed.min(axis=1)
    sel = jnp.where(best < sentinel, best % q, -1)
    return n_act.astype(jnp.int32), th.astype(jnp.int32), pause, \
        sel.astype(jnp.int32)


def bfc_fused_ref(occ, qpaused, ptr, blocked, *, pause_window: int,
                  scheduler: str = "drr", srf_key=None):
    """Oracle for `bfc_step.bfc_fused`: threshold + DRR/SRF pick +
    occupancy update (see its docstring for the operand contract)."""
    p, q = occ.shape
    active = (occ > 0) & ~qpaused
    n_act = jnp.maximum(active.sum(axis=1), 1)
    th = pause_threshold(n_act, pause_window)
    pause = occ > th[:, None]
    q_ix = jnp.arange(q, dtype=jnp.int32)[None, :]
    if scheduler == "srf":
        key, max_key = srf_key, BIG
    else:
        key, max_key = (q_ix - ptr[:, None]) % q, q - 1
    sentinel = packed_sentinel(q, max_key)
    elig = active & ~blocked[:, None]
    packed = jnp.where(elig, key * q + q_ix, sentinel)
    best = packed.min(axis=1)
    can_tx = best < sentinel
    sel = jnp.where(can_tx, best % q, -1).astype(jnp.int32)
    occ_after = occ - (can_tx[:, None]
                       & (q_ix == sel[:, None])).astype(jnp.int32)
    return (n_act.astype(jnp.int32), th.astype(jnp.int32), pause, sel,
            can_tx, occ_after)
