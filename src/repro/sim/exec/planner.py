"""Device-aware execution planning for batched sweep grids.

The planner answers one question: given a grid of K batch lanes whose
per-lane device footprint is `sweep.lane_state_bytes`, how wide should each
dispatch be and on which devices should it land? Callers no longer guess a
`max_batch_bytes` — `plan()` derives the chunk width itself.

Budget derivation order (first readable source wins; `auto_budget_bytes`
reports which as `ExecPlan.budget_source`):

1. ``caller`` — an explicit integer budget (the old ``max_batch_bytes``)
   always wins;
2. ``env`` — ``REPRO_EXEC_MAX_BYTES`` overrides from the environment;
3. ``memory_stats`` — accelerators report ``device.memory_stats()``
   (``bytes_limit`` - ``bytes_in_use``): chunks shard *evenly*, so the
   budget is min-free x device count — the least-free device binds the
   whole set. An accelerator that reports no limit is an error;
4. ``host_meminfo`` — host-platform devices (CPU, incl.
   ``xla_force_host_platform_device_count`` splits) are slices of one RAM
   pool, read from ``/proc/meminfo`` MemAvailable;
5. ``uncapped`` — CPU devices on a host with no readable MemAvailable:
   the whole grid in one dispatch.

A fraction (`DEFAULT_MEM_FRACTION`, 0.8) of the readable figure is
budgeted so compiler scratch and host buffers keep headroom.

`pipeline_depth` semantics: it is the number of chunks the dispatcher
keeps in flight *simultaneously* (1 = fully synchronous, 2 = classic
double buffer — chunk i+1 computes while chunk i is pulled back to host).
Every in-flight chunk is device-resident, so a grid that must be chunked
sizes each chunk to ``budget / pipeline_depth`` bytes; deeper pipelines
buy more compute/readback overlap at the price of narrower chunks.

The per-lane figure comes from `sweep.lane_state_bytes`, which walks the
exact shapes `engine.make_step(dims, …)` allocates — including the
``dims.prop_max``-padded wire rings and feedback delay lines — so a
mixed-latency batch padded to a long wire is billed at the padded size
and the chunk width shrinks proportionally.

On a multi-device host the chunk width is a multiple of the device count —
each dispatch shards its lanes evenly across the devices (see
`exec.dispatch`) — and a budget too small for one lane per device shrinks
the device set instead of overrunning the budget.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple, Union

import jax

from ..engine import DEFAULT_SEGMENT
from ..topology import TopoDims

ENV_BUDGET = "REPRO_EXEC_MAX_BYTES"
DEFAULT_MEM_FRACTION = 0.8
MEMINFO_PATH = "/proc/meminfo"
DEFAULT_PIPELINE_DEPTH = 2


@dataclass(frozen=True)
class RetryPolicy:
    """Bounded per-chunk recovery from RESOURCE_EXHAUSTED failures.

    When a chunk's dispatch or landing OOMs, the dispatcher re-runs that
    chunk's lanes in narrower sub-chunks: each failed attempt halves the
    width (never below `min_width`, i.e. degrading gracefully to width-1
    single-lane dispatches) and sleeps ``backoff_s * 2**attempt`` before
    retrying. `max_retries` bounds the total failed attempts per chunk —
    the retry state is a (width, attempt, offset) triple, bounded by
    construction — after which the dispatcher surfaces a structured
    `faults.ExecError` naming the lanes it could not land. Only the
    failing chunk pays: sibling chunks keep their planned width, and a
    fault-free run takes this code path zero times (asserted by
    scripts/trace_guard.py)."""
    max_retries: int = 4
    min_width: int = 1
    backoff_s: float = 0.0

    def backoff_for(self, attempt: int) -> float:
        """Exponential backoff delay before retry `attempt` (0-based)."""
        return self.backoff_s * (2 ** attempt)


def host_available_bytes(path: str = MEMINFO_PATH) -> Optional[int]:
    """MemAvailable from a /proc/meminfo-format file, or None."""
    try:
        with open(path) as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None
    return None


def device_free_bytes(dev) -> int:
    """Free bytes an accelerator reports via memory_stats(). A device that
    cannot report them is an error: budgeting it from host RAM would
    hide which device the run is on."""
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit", stats.get("bytes_reservable_limit"))
    if limit is None:
        raise RuntimeError(
            f"{dev.platform} device {dev} reports no memory limit in "
            f"memory_stats() ({stats!r}); pass an explicit budget")
    return max(0, int(limit) - int(stats.get("bytes_in_use", 0)))


def auto_budget_bytes(devices: Sequence,
                      fraction: float = DEFAULT_MEM_FRACTION,
                      env: str = ENV_BUDGET,
                      meminfo: str = MEMINFO_PATH,
                      ) -> Tuple[Optional[int], str]:
    """(total device-resident byte budget, source) for a device set.

    Source is one of 'env', 'memory_stats', 'host_meminfo', 'uncapped';
    only CPU devices take 'host_meminfo' or 'uncapped'."""
    env_val = os.environ.get(env)
    if env_val:
        return int(env_val), "env"
    if all(d.platform == "cpu" for d in devices):
        # host-platform devices are slices of one RAM pool: budget the pool
        host = host_available_bytes(meminfo)
        if host is None:
            return None, "uncapped"
        return int(host * fraction), "host_meminfo"
    # chunks shard EVENLY across devices, so the least-free device is the
    # binding constraint — min * n, not sum (a lopsided pair would
    # otherwise OOM the small device)
    free = [device_free_bytes(d) for d in devices]
    return int(min(free) * len(free) * fraction), "memory_stats"


@dataclass(frozen=True)
class ExecPlan:
    """Where and how wide a sweep grid executes.

    One plan covers one `run_batch` call (one protocol variant, one program
    signature): K lanes run as ceil(K / chunk_width) dispatches of
    `chunk_width` lanes each, every dispatch sharded evenly across
    `devices` (chunk_width is a multiple of the device count), with up to
    `pipeline_depth` dispatches in flight so host readback of chunk i
    overlaps device compute of chunk i+1."""
    n_lanes: int
    chunk_width: int
    devices: tuple
    per_lane_bytes: int
    budget_bytes: Optional[int]
    budget_source: str
    pipeline_depth: int
    dims: TopoDims
    f_max: int
    n_ticks: int
    unroll: int = 1
    # active-horizon runner knobs (static: part of the compile-cache key,
    # so every plan of one sweep must agree on them). `segment` is the tick
    # width between quiescence checks; `early_exit` False forces the flat
    # scan (the A/B escape hatch).
    segment: int = DEFAULT_SEGMENT
    early_exit: bool = True
    # per-chunk OOM recovery budget (see `RetryPolicy`); the dispatcher
    # consults it only when a chunk actually fails, so it never shapes the
    # compiled program or the fault-free fast path.
    retry: RetryPolicy = RetryPolicy()

    @property
    def n_devices(self) -> int:
        return len(self.devices)

    @property
    def sharded(self) -> bool:
        return self.n_devices > 1

    @property
    def n_chunks(self) -> int:
        return -(-self.n_lanes // self.chunk_width)

    @property
    def lanes_per_device(self) -> int:
        return self.chunk_width // self.n_devices

    def describe(self) -> str:
        budget = ("uncapped" if self.budget_bytes is None
                  else f"{self.budget_bytes / 2**20:.0f} MiB")
        runner = (f"segment {self.segment}" if self.early_exit
                  else "flat scan (early exit off)")
        return (f"ExecPlan: {self.n_lanes} lanes -> {self.n_chunks} "
                f"chunk(s) x {self.chunk_width} lanes on {self.n_devices} "
                f"device(s) [{self.lanes_per_device}/dev], "
                f"{self.per_lane_bytes / 2**20:.1f} MiB/lane, budget "
                f"{budget} ({self.budget_source}), pipeline depth "
                f"{self.pipeline_depth}, {runner}")


@functools.lru_cache(maxsize=None)
def _lane_bytes(dims: TopoDims, scfg, f_max: int, n_ticks: int) -> int:
    from .. import sweep
    return sweep.lane_state_bytes(dims, scfg, f_max, n_ticks)


def plan(dims: TopoDims, cfg, f_max: int, n_ticks: int, n_lanes: int, *,
         devices: Optional[Sequence] = None,
         budget: Union[int, str, None] = "auto",
         pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
         unroll: int = 1, segment: int = DEFAULT_SEGMENT,
         early_exit: bool = True,
         retry: Optional[RetryPolicy] = None) -> ExecPlan:
    """Derive an `ExecPlan` for an `n_lanes`-wide grid of one program
    signature. `budget` is an explicit total byte cap, "auto" (read device /
    host memory stats), or None (uncapped). `devices` defaults to every
    local device. `segment` / `early_exit` configure the engine's
    active-horizon runner (see `engine.compiled_runner`); `retry` the
    per-chunk OOM recovery budget (default `RetryPolicy()`)."""
    from .. import engine
    devices = tuple(devices if devices is not None else jax.devices())
    if not devices:
        raise ValueError("empty device set")
    per_lane = _lane_bytes(dims, engine.static_cfg(cfg), f_max, n_ticks)

    if budget == "auto":
        budget_bytes, source = auto_budget_bytes(devices)
    elif budget is None:
        budget_bytes, source = None, "uncapped"
    else:
        budget_bytes, source = int(budget), "caller"

    width = n_lanes
    if budget_bytes is not None and n_lanes * per_lane > budget_bytes:
        # chunked execution keeps up to pipeline_depth chunks device-
        # resident at once, so each chunk may claim only its share of the
        # budget (a single-chunk grid has nothing else in flight)
        eff = budget_bytes // max(1, pipeline_depth)
        width = max(1, min(n_lanes, eff // max(per_lane, 1)))

    if len(devices) > 1:
        if width < len(devices):
            # budget affords fewer lanes than devices: shrink the device
            # set rather than overrun the budget
            devices = devices[:width]
        else:
            # every dispatch shards evenly: round UP to a device multiple
            # unless that would bust an explicit budget (then round down)
            d = len(devices)
            up = -(-width // d) * d
            if budget_bytes is None or up * per_lane <= budget_bytes:
                width = up
            else:
                width = (width // d) * d

    return ExecPlan(n_lanes=n_lanes, chunk_width=width, devices=devices,
                    per_lane_bytes=per_lane, budget_bytes=budget_bytes,
                    budget_source=source, pipeline_depth=pipeline_depth,
                    dims=dims, f_max=f_max, n_ticks=n_ticks, unroll=unroll,
                    segment=segment, early_exit=early_exit,
                    retry=retry if retry is not None else RetryPolicy())
