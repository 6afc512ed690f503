"""Jitted wrappers + implementation resolution for the BFC switch kernels.

Resolution order for ``impl`` (shared by the standalone `decide` wrapper
and the engine's `ProtoConfig.kernel_impl` flag via `resolve_impl`):

1. the ``REPRO_KERNEL`` environment variable, when set to a concrete
   implementation (``lax``/``ref``, ``pallas``, ``interpret``), overrides
   whatever the caller or config asked for (``auto`` in the env means "no
   override");
2. ``auto`` resolves to the compiled Pallas kernel (``pallas``) on a TPU
   backend;
3. off-TPU, ``auto`` resolves to the Pallas kernel in interpret mode when
   ``REPRO_KERNEL_INTERPRET=1`` — the CI/test toggle that makes the
   kernel *body* execute on CPU/GPU (without it, ``auto`` historically
   meant the Pallas path was never exercised outside TPU);
4. otherwise ``auto`` falls back to the caller's lax/jnp path (``ref``
   here, ``lax`` in the engine).

Env resolution happens OUTSIDE jit — `decide`/`fused` re-read the
environment on every call and pass a concrete impl to the jitted inner
function — so toggling ``REPRO_KERNEL*`` between calls can never hit a
stale jit cache keyed on ``"auto"``.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax

from .bfc_step import bfc_decide, bfc_fused
from .ref import bfc_decide_ref, bfc_fused_ref

ENV_IMPL = "REPRO_KERNEL"
ENV_INTERPRET = "REPRO_KERNEL_INTERPRET"
_IMPLS = ("auto", "lax", "ref", "pallas", "interpret")


def resolve_impl(impl: str = "auto", *, lax_name: str = "ref") -> str:
    """Resolve an impl request to a concrete implementation name (see the
    module docstring for the order). `lax_name` is what the caller calls
    its non-Pallas path: 'ref' (this module's oracle) or 'lax' (the
    engine's inline phase pipeline); 'lax' and 'ref' requests normalize to
    it either way."""
    env = os.environ.get(ENV_IMPL, "").strip().lower()
    if env and env != "auto":
        impl = env
    if impl not in _IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; expected one of "
                         f"{_IMPLS}")
    if impl in ("lax", "ref"):
        return lax_name
    if impl == "auto":
        if jax.default_backend() == "tpu":
            return "pallas"
        if os.environ.get(ENV_INTERPRET, "").strip() == "1":
            return "interpret"
        return lax_name
    return impl


@contextlib.contextmanager
def forced(impl: str):
    """Route every switch decision through `impl` for the duration: sets
    ``REPRO_KERNEL``, which overrides `ProtoConfig.kernel_impl` in
    `resolve_impl`. An empty `impl` changes nothing."""
    prev = os.environ.get(ENV_IMPL)
    if impl:
        os.environ[ENV_IMPL] = impl
    try:
        yield
    finally:
        if impl:
            if prev is None:
                os.environ.pop(ENV_IMPL, None)
            else:
                os.environ[ENV_IMPL] = prev


@functools.partial(jax.jit, static_argnames=("pause_window", "impl",
                                             "block_p"))
def _decide(occ, qpaused, ptr, *, pause_window: int, impl: str,
            block_p: int):
    if impl == "ref":
        return bfc_decide_ref(occ, qpaused, ptr, pause_window=pause_window)
    return bfc_decide(occ, qpaused, ptr, pause_window=pause_window,
                      block_p=block_p, interpret=(impl == "interpret"))


def decide(occ, qpaused, ptr, *, pause_window: int, impl: str = "auto",
           block_p: int = 256):
    return _decide(occ, qpaused, ptr, pause_window=pause_window,
                   impl=resolve_impl(impl), block_p=block_p)


@functools.partial(jax.jit, static_argnames=("pause_window", "scheduler",
                                             "impl", "block_p"))
def _fused(occ, qpaused, ptr, blocked, srf_key, *, pause_window: int,
           scheduler: str, impl: str, block_p: int):
    if impl == "ref":
        return bfc_fused_ref(occ, qpaused, ptr, blocked,
                             pause_window=pause_window,
                             scheduler=scheduler, srf_key=srf_key)
    return bfc_fused(occ, qpaused, ptr, blocked, pause_window=pause_window,
                     scheduler=scheduler, srf_key=srf_key, block_p=block_p,
                     interpret=(impl == "interpret"))


def fused(occ, qpaused, ptr, blocked, *, pause_window: int,
          scheduler: str = "drr", srf_key=None, impl: str = "auto",
          block_p: int = 256):
    """The engine's fused switch step (threshold + DRR/SRF pick +
    occupancy update); see `bfc_step.bfc_fused` for the operand contract.
    `impl` resolves per the module docstring; an engine caller passes the
    already-resolved `ProtoConfig.kernel_impl` (resolution is idempotent).
    """
    return _fused(occ, qpaused, ptr, blocked, srf_key,
                  pause_window=pause_window, scheduler=scheduler,
                  impl=resolve_impl(impl), block_p=block_p)
