"""Flash attention forward kernel (Pallas / TPU).

Tiling: grid = (batch, q_heads, q_blocks, kv_blocks); the kv_blocks axis is
'arbitrary' (sequential) so the online-softmax accumulators live in VMEM
scratch across kv iterations. Block shapes are MXU-aligned (block_q x hd,
block_k x hd). GQA is handled by indexing the kv head as q_head // group in
the BlockSpec index maps, so kv tiles are reused across the q-head group.

VMEM working set per grid step:
    q (block_q, hd) + k,v (block_k, hd) + acc (block_q, hd) f32
  = (block_q + 2*block_k + 2*block_q) * hd * 4B  ~ 0.4 MB at 128/128/128,
well inside the ~16 MB VMEM budget, leaving room for double buffering.

Supports causal masking and sliding windows (fully-masked kv blocks are
skipped with pl.when). Validated against ref.py with interpret=True on CPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level skip: fully-masked kv blocks do no work
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window > 0:
        run &= k_start + block_k > q_start - window + 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (block_q, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (block_k, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or window > 0:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask &= kpos <= qpos
            if window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q (B,H,S,hd), k/v (B,K,T,hd) -> (B,H,S,hd). GQA via H % K == 0."""
    b, h, s, hd = q.shape
    _, kh, t, _ = k.shape
    assert h % kh == 0, (h, kh)
    g = h // kh
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0
    nq, nk = s // block_q, t // block_k
    scale = 1.0 / math.sqrt(hd)

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             window=window, block_q=block_q, block_k=block_k,
                             nk=nk)
    return pl.pallas_call(
        kern,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, hi, qi, ki: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
