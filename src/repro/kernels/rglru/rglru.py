"""RG-LRU chunked linear-recurrence kernel (Pallas / TPU).

    h_t = exp(log_a_t) * h_{t-1} + b_t        (per channel)

TPU adaptation: instead of a length-S sequential scan (latency-bound on the
VPU), each chunk of C tokens is solved in closed form with lower-triangular
(C x C) matmuls that run on the MXU:

    cs    = cumsum(log_a)            (via tril-ones matmul)
    h_i   = exp(cs_i) h_0 + sum_{j<=i} exp(cs_i - cs_j) b_j

Grid = (batch, width_blocks); the sequential chunk loop runs inside the
kernel with the carry h held in VMEM scratch. VMEM per step: 3 x (S, bw)
f32 blocks; with S<=4096, bw=128 that is 6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(la_ref, b_ref, h0_ref, o_ref, hT_ref, h_scr, *, chunk: int,
            nc: int, bw: int):
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))       # cumsum matmul
    tri_s = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))

    h_scr[...] = h0_ref[0].astype(jnp.float32)                  # (bw,) block

    def body(c, h):
        sl = pl.ds(c * chunk, chunk)
        la = la_ref[0, sl, :].astype(jnp.float32)               # (C, bw)
        bb = b_ref[0, sl, :].astype(jnp.float32)
        cs = jax.lax.dot_general(tri, la, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # w_ij = exp(cs_i - cs_j) for j <= i ; contract over j per channel:
        # done channel-blocked as (C,C) x (C,bw) after factoring exp:
        #   inner_i = exp(cs_i) * sum_j tril_ij * exp(-cs_j) * b_j
        # exp(-cs_j) can overflow for strong decay; RG-LRU decays are bounded
        # (log_a >= -0.1 typical), so C * |log_a| stays < 30 for C = 128.
        e_neg = jnp.exp(-cs) * bb
        summed = jax.lax.dot_general(tri_s, e_neg, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        h_all = jnp.exp(cs) * (summed + h[None, :])
        o_ref[0, sl, :] = h_all.astype(o_ref.dtype)
        return h_all[-1]

    h = jax.lax.fori_loop(0, nc, body, h_scr[...])
    hT_ref[0] = h.astype(hT_ref.dtype)


def rglru_scan(log_a, b, h0, *, chunk: int = 128, block_w: int = 128,
               interpret: bool = False):
    """log_a, b: (B,S,W); h0: (B,W) -> (h_all (B,S,W), h_last (B,W)).

    Note the exp(-cs) factorization bounds |log_a * chunk| < 80; callers clip
    log_a accordingly (the model's parameterization keeps log_a in (-0.1, 0)).
    """
    bsz, s, w = b.shape
    block_w = min(block_w, w)
    chunk = min(chunk, s)
    assert w % block_w == 0 and s % chunk == 0
    nc = s // chunk

    kern = functools.partial(_kernel, chunk=chunk, nc=nc, bw=block_w)
    grid = (bsz, w // block_w)
    out, hT = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, block_w), lambda bi, wi: (bi, 0, wi)),
            pl.BlockSpec((1, s, block_w), lambda bi, wi: (bi, 0, wi)),
            pl.BlockSpec((1, block_w), lambda bi, wi: (bi, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, block_w), lambda bi, wi: (bi, 0, wi)),
            pl.BlockSpec((1, block_w), lambda bi, wi: (bi, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, w), jnp.float32),
            jax.ShapeDtypeStruct((bsz, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(log_a, b, h0)
    return out, hT
