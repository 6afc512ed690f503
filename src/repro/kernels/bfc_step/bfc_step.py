"""BFC switch dataplane decision kernel (Pallas / TPU).

The per-tick, per-egress-port hot loop of the BFC switch (paper §3.3.2):
given queue occupancies and pause bits for a block of ports,

  1. N_active  = #queues with data and not paused          (VPU reduction)
  2. Th        = ceil(pause_window / N_active)             (threshold)
  3. pause     = occupancy > Th                            (per queue)
  4. DRR pick  = argmin over eligible queues of (q - ptr) mod Q

This is the TPU-native reading of "per-packet line-rate state update":
ports are batched into VMEM-resident blocks (block_p x Q int32 tiles, lanes =
queues) and the whole decision vector for 100s of ports is computed in one
grid step — the simulator's inner loop offloaded as a kernel. ref.py is the
pure-jnp oracle (identical math used by repro.sim.engine).

Two entry points:

* `bfc_decide`   — the standalone decision kernel (threshold + DRR pick).
* `bfc_fused`    — the engine's kernelized switch step (ROADMAP item 3):
  the fused pause-threshold + DRR/SRF-pick + queue-occupancy-update the
  phase pipeline calls each tick when `ProtoConfig.kernel_impl` selects
  the kernel path. Under `sim/sweep.py`'s vmap the batch lane becomes an
  extra grid axis, so a whole sweep chunk's switch decisions run as one
  kernel launch per tick. Port counts that do not divide `block_p` (e.g.
  P=98 from an oversubscribed Clos) are padded with inert rows (occ=0,
  paused/blocked=True) and trimmed from every output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import BIG, packed_sentinel, pause_threshold


def _pad_ports(p: int, block_p: int, *rows):
    """Pad the port axis of each (P,)/(P, Q) array up to a block multiple
    with inert rows (the caller picks per-array pad values): padded ports
    carry occ=0 and paused/blocked=True, so they never transmit, never
    pause, and their outputs are trimmed before returning."""
    pp = -(-p // block_p) * block_p
    if pp == p:
        return [a for a, _ in rows]
    return [jnp.pad(a, ((0, pp - p),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=v) for a, v in rows]


def _kernel(occ_ref, qpaused_ref, ptr_ref, o_nact, o_th, o_pause, o_sel, *,
            pause_window: int, nq: int):
    occ = occ_ref[...]                          # (bp, Q) int32
    qpaused = qpaused_ref[...]                  # (bp, Q) bool
    ptr = ptr_ref[...]                          # (bp, 1) int32

    active = (occ > 0) & jnp.logical_not(qpaused)
    n_act = jnp.maximum(jnp.sum(active.astype(jnp.int32), axis=1,
                                keepdims=True), 1)
    th = pause_threshold(n_act, pause_window)
    o_nact[...] = n_act
    o_th[...] = th
    o_pause[...] = occ > th

    q_ix = jax.lax.broadcasted_iota(jnp.int32, occ.shape, 1)
    drr_key = (q_ix - ptr) % nq
    sentinel = packed_sentinel(nq, nq - 1)
    packed = jnp.where(active, drr_key * nq + q_ix, sentinel)
    best = jnp.min(packed, axis=1, keepdims=True)
    o_sel[...] = jnp.where(best < sentinel, best % nq, -1)


def bfc_decide(occ, qpaused, ptr, *, pause_window: int, block_p: int = 256,
               interpret: bool = False):
    """occ (P,Q) i32, qpaused (P,Q) bool, ptr (P,) i32 ->
    (n_active (P,), th (P,), pause_mask (P,Q) bool, sel_q (P,) i32)."""
    p, q = occ.shape
    block_p = min(block_p, p)
    occ, qpaused, ptr = _pad_ports(p, block_p, (occ, 0), (qpaused, True),
                                   (ptr, 0))
    pp = occ.shape[0]
    kern = functools.partial(_kernel, pause_window=pause_window, nq=q)
    nact, th, pause, sel = pl.pallas_call(
        kern,
        grid=(pp // block_p,),
        in_specs=[
            pl.BlockSpec((block_p, q), lambda i: (i, 0)),
            pl.BlockSpec((block_p, q), lambda i: (i, 0)),
            pl.BlockSpec((block_p, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_p, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_p, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_p, q), lambda i: (i, 0)),
            pl.BlockSpec((block_p, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pp, 1), jnp.int32),
            jax.ShapeDtypeStruct((pp, 1), jnp.int32),
            jax.ShapeDtypeStruct((pp, q), jnp.bool_),
            jax.ShapeDtypeStruct((pp, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(occ, qpaused, ptr[:, None])
    return nact[:p, 0], th[:p, 0], pause[:p], sel[:p, 0]


def _fused_kernel(occ_ref, qpaused_ref, ptr_ref, blocked_ref, *refs,
                  pause_window: int, nq: int, scheduler: str):
    if scheduler == "srf":
        key_ref, refs = refs[0], refs[1:]
    o_nact, o_th, o_pause, o_sel, o_cantx, o_occ = refs
    occ = occ_ref[...]                          # (bp, Q) int32
    qpaused = qpaused_ref[...]                  # (bp, Q) bool
    ptr = ptr_ref[...]                          # (bp, 1) int32
    blocked = blocked_ref[...]                  # (bp, 1) bool

    active = (occ > 0) & jnp.logical_not(qpaused)
    n_act = jnp.maximum(jnp.sum(active.astype(jnp.int32), axis=1,
                                keepdims=True), 1)
    th = pause_threshold(n_act, pause_window)
    o_nact[...] = n_act
    o_th[...] = th
    o_pause[...] = occ > th

    q_ix = jax.lax.broadcasted_iota(jnp.int32, occ.shape, 1)
    if scheduler == "srf":
        key, max_key = key_ref[...], BIG        # caller clamps to BIG
    else:
        key, max_key = (q_ix - ptr) % nq, nq - 1
    sentinel = packed_sentinel(nq, max_key)
    elig = active & jnp.logical_not(blocked)
    packed = jnp.where(elig, key * nq + q_ix, sentinel)
    best = jnp.min(packed, axis=1, keepdims=True)
    can_tx = best < sentinel
    sel = jnp.where(can_tx, best % nq, -1)
    o_sel[...] = sel
    o_cantx[...] = can_tx
    o_occ[...] = occ - (can_tx & (q_ix == sel)).astype(jnp.int32)


def bfc_fused(occ, qpaused, ptr, blocked, *, pause_window: int,
              scheduler: str = "drr", srf_key=None, block_p: int = 256,
              interpret: bool = False):
    """Fused per-tick switch step: threshold + scheduler pick + occupancy
    update in one kernel.

    occ (P,Q) i32, qpaused (P,Q) bool, ptr (P,) i32, blocked (P,) bool
    (PFC-paused or NIC ports — excluded from the pick but NOT from
    N_active, mirroring `phases.derive` + `phases.switch_tx`);
    srf_key (P,Q) i32 (required iff scheduler == 'srf'; pre-clamped to
    `BIG` by the caller, exactly as the lax path clamps `qsrf`) ->
    (n_active (P,), th (P,), pause_mask (P,Q) bool, sel_q (P,) i32
    (-1 = nothing eligible), can_tx (P,) bool, occ_after (P,Q) i32)."""
    p, q = occ.shape
    block_p = min(block_p, p)
    pads = [(occ, 0), (qpaused, True), (ptr, 0), (blocked, True)]
    if scheduler == "srf":
        assert srf_key is not None, "srf scheduler needs srf_key"
        pads.append((srf_key, BIG))
    padded = _pad_ports(p, block_p, *pads)
    occ, qpaused, ptr, blocked = padded[:4]
    pp = occ.shape[0]
    kern = functools.partial(_fused_kernel, pause_window=pause_window,
                             nq=q, scheduler=scheduler)
    wide = pl.BlockSpec((block_p, q), lambda i: (i, 0))
    narrow = pl.BlockSpec((block_p, 1), lambda i: (i, 0))
    in_specs = [wide, wide, narrow, narrow]
    inputs = [occ, qpaused, ptr[:, None], blocked[:, None]]
    if scheduler == "srf":
        in_specs.append(wide)
        inputs.append(padded[4])
    nact, th, pause, sel, cantx, occ_after = pl.pallas_call(
        kern,
        grid=(pp // block_p,),
        in_specs=in_specs,
        out_specs=[narrow, narrow, wide, narrow, narrow, wide],
        out_shape=[
            jax.ShapeDtypeStruct((pp, 1), jnp.int32),
            jax.ShapeDtypeStruct((pp, 1), jnp.int32),
            jax.ShapeDtypeStruct((pp, q), jnp.bool_),
            jax.ShapeDtypeStruct((pp, 1), jnp.int32),
            jax.ShapeDtypeStruct((pp, 1), jnp.bool_),
            jax.ShapeDtypeStruct((pp, q), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*inputs)
    return (nact[:p, 0], th[:p, 0], pause[:p], sel[:p, 0], cantx[:p, 0],
            occ_after[:p])
