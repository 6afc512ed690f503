"""The main path's programs compile for a TPU v5e that is described, not
attached: the fused switch-step kernel at the paper fabric's port count, a
ragged port count and under a 4-lane vmap, and the whole batched simulator
program on the Pallas path, on one chip and with its lanes split over
four. Each compiled program must hold the kernel
(`tpu_custom_call`). A compile that passes is not a chip run; it catches
what the TPU compiler refuses (tiling, fast-memory limits, lowering) at
no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.kernels.bfc_step.bfc_step import bfc_fused
from repro.sim import engine, sweep
from repro.sim.config import ProtoConfig, TimingParams
from repro.sim.topology import TopoDims
from repro.sim.trace import golden

pytestmark = pytest.mark.tier1

PAUSE_WINDOW = TimingParams().pause_window
KERNEL_MARK = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("scheduler,p,lanes", [
    ("drr", 384, None), ("srf", 384, None),      # the paper fabric's ports
    ("drr", 98, None), ("srf", 98, None),        # ragged: padded to a block
    ("drr", 384, 4), ("srf", 384, 4),            # a sweep chunk's vmap
    ("drr", 768, None),                          # the k = 8 fat-tree's ports
])
def test_bfc_fused_compiles_for_v5e(one_chip, scheduler, p, lanes):
    q = 32
    lead = () if lanes is None else (lanes,)
    args = [_sds(lead + (p, q), jnp.int32, one_chip),
            _sds(lead + (p, q), jnp.bool_, one_chip),
            _sds(lead + (p,), jnp.int32, one_chip),
            _sds(lead + (p,), jnp.bool_, one_chip)]
    if scheduler == "srf":
        args.append(_sds(lead + (p, q), jnp.int32, one_chip))

    def step(occ, qpaused, ptr, blocked, key=None):
        return bfc_fused(occ, qpaused, ptr, blocked,
                         pause_window=PAUSE_WINDOW, scheduler=scheduler,
                         srf_key=key)

    fn = step if lanes is None else jax.vmap(step)
    compiled = jax.jit(fn).lower(*args).compile()
    assert KERNEL_MARK in compiled.as_text()


def _compile_golden_runner(lanes: int, sharding, devices=None):
    """The whole traced golden-case program on the Pallas path, `lanes`
    wide: what the golden phase of chip_smoke.py runs per family."""
    topo, flows = golden.golden_case()
    cfg = golden.golden_cfg(ProtoConfig(kernel_impl="pallas"))
    dims = TopoDims.of(topo)
    f_max = sweep.padded_count([flows])
    go = engine.compiled_runner(dims, engine.static_cfg(cfg), f_max,
                                golden.GOLDEN_N_TICKS, batched=True,
                                devices=devices)
    operands = (sweep.stack_operands([flows] * lanes, cfg, f_max),
                sweep.stack_topos([topo] * lanes, cfg, dims))
    shapes = jax.tree_util.tree_map(
        lambda x: _sds(x.shape, x.dtype, sharding), operands)
    return go.lower(*shapes).compile()


@pytest.fixture(scope="module")
def batched_runner(one_chip):
    return _compile_golden_runner(2, one_chip)


def test_batched_runner_compiles_for_v5e(batched_runner):
    assert KERNEL_MARK in batched_runner.as_text()
    mem = batched_runner.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**30


def test_compiled_kernel_keeps_its_name_under_its_scope(batched_runner):
    """The scopes are metadata: the kernel is still the `_fused` custom
    call the benchmark's kernel metrics find by name, and its op_name puts
    it in `switch_decision` inside `phase.derive`."""
    calls = [line for line in batched_runner.as_text().splitlines()
             if KERNEL_MARK in line]
    assert calls and all(
        re.match(r"\s*(ROOT )?%_fused(\.\d+)? = ", line) for line in calls)
    paths = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in calls]
    assert all("phase.derive/switch_decision/" in p for p in paths)


def test_lane_sharded_runner_compiles_for_v5e_2x2(topo):
    """Lanes split over four chips: XLA cannot partition a Mosaic kernel,
    so the runner must shard_map the batch axis; no collective may
    cross chips."""
    mesh = Mesh(np.asarray(topo.devices), ("lanes",))
    compiled = _compile_golden_runner(
        4, NamedSharding(mesh, PartitionSpec("lanes")), topo.devices)
    text = compiled.as_text()
    assert KERNEL_MARK in text
    assert "all-reduce" not in text and "all-gather" not in text
