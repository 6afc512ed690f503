"""RWKV-6 WKV recurrence kernel (Pallas / TPU), chunked linear attention.

Per head (D = head_dim, typically 64):
    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

Grid = (batch, heads); the (D x D) state lives in VMEM scratch across the
in-kernel chunk loop. Within a chunk of C tokens everything is (C x D) /
(C x C) matmuls (MXU): decays enter as exp(cumsum(log w)) factors, the
intra-chunk attention is a strictly-lower-triangular masked (C x C) product,
and the u-bonus is the diagonal. Matches repro.models.rwkv6.wkv_chunked
(the jnp oracle) to ~1e-5.

Numerics: k is scaled by exp(-cs_j); callers clip log w to [-5, 0) so the
exponent stays < C*5 = 80 < log(f32 max) at C = 16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, h0_ref, o_ref, hT_ref,
            s_scr, *, chunk: int, nc: int, dd: int):
    tri_cum = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))      # inclusive
    tri_lo = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), k=-1)

    s_scr[...] = h0_ref[0, 0].astype(jnp.float32)                  # (D, D)

    def body(c, state):
        sl = pl.ds(c * chunk, chunk)
        rc = r_ref[0, sl, 0, :].astype(jnp.float32)                # (C, D)
        kc = k_ref[0, sl, 0, :].astype(jnp.float32)
        vc = v_ref[0, sl, 0, :].astype(jnp.float32)
        lw = lw_ref[0, sl, 0, :].astype(jnp.float32)
        u = u_ref[0, :].astype(jnp.float32)                        # (D,)

        cs = jax.lax.dot_general(tri_cum, lw, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        decay_to_i = jnp.exp(cs - lw)           # product of w over 1..i-1
        r_dec = rc * decay_to_i
        inter = jax.lax.dot_general(r_dec, state, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        k_scaled = kc * jnp.exp(-cs)
        att = jax.lax.dot_general(r_dec, k_scaled, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        att = att * tri_lo
        intra = jax.lax.dot_general(att, vc, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        diag = jnp.sum(rc * u[None, :] * kc, axis=1, keepdims=True)
        out = inter + intra + diag * vc
        o_ref[0, sl, 0, :] = out.astype(o_ref.dtype)

        total = cs[-1:, :]                       # (1, D)
        k_dec = kc * jnp.exp(total - cs)
        upd = jax.lax.dot_general(k_dec, vc, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return jnp.exp(total[0])[:, None] * state + upd

    state = jax.lax.fori_loop(0, nc, body, s_scr[...])
    hT_ref[0, 0] = state.astype(hT_ref.dtype)


def wkv(r, k, v, logw, u, h0, *, chunk: int = 16, interpret: bool = False):
    """r,k,v,logw: (B,S,H,D); u: (H,D); h0: (B,H,D,D).
    Returns (out (B,S,H,D) f32, hT (B,H,D,D) f32)."""
    b, s, h, dd = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    kern = functools.partial(_kernel, chunk=chunk, nc=nc, dd=dd)
    out, hT = pl.pallas_call(
        kern,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, s, 1, dd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, dd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, dd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, s, 1, dd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, dd), lambda bi, hi: (hi, 0)),
            pl.BlockSpec((1, 1, dd, dd), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, s, 1, dd), lambda bi, hi: (bi, 0, hi, 0)),
            pl.BlockSpec((1, 1, dd, dd), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, dd), jnp.float32),
            jax.ShapeDtypeStruct((b, h, dd, dd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dd, dd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(r, k, v, logw, u, h0)
    return out, hT
