"""Device-aware execution layer for the batched sweep subsystem.

`sim/sweep.py` decides *what* runs (padding contracts, operand stacking,
one compilation per protocol variant); this package decides *where and how
fast* it runs:

* `planner`  — reads live device stats (`jax.devices()`, `memory_stats()`,
  host MemAvailable) and the measured per-lane SimState footprint —
  including the `prop_max`-padded wire/feedback rings of mixed-latency
  batches — to derive an `ExecPlan`: chunk width, device set, pipeline
  depth (= chunks kept device-resident in flight). No more caller-guessed
  `max_batch_bytes`; see `planner`'s docstring for the budget derivation
  order.
* `dispatch` — executes a plan: each chunk's lanes shard evenly across the
  devices via a batch-axis `NamedSharding` of the ONE cached executable,
  and chunks double-buffer so host readback overlaps device compute.
* `store`    — spools landed chunks (and their opt-in trace blocks, see
  `sim/trace/`) to disk incrementally and records the perf trajectory as
  `BENCH_sweep.json`.

* `faults`   — deterministic fault injection (`REPRO_FAULTS` /
  `FaultSpec`) so every failure path above — chunk OOM, crash or kill
  mid-spool — is a reproducible event in tests and in the
  `scripts/fault_guard.py` CI gate, plus the structured `ExecError` the
  dispatcher raises when a chunk's bounded retry budget is spent.

`sweep.run_batch` / `run_grid` / `scenarios.run` route through `plan()` +
`execute()`; an interrupted spooled run restarts through `resume()`; see
docs/ARCHITECTURE.md ("The execution layer", "Fault tolerance & resume").
"""
from .dispatch import (ACTIVE_LOG, BoundedLog, RETRY_LOG,  # noqa: F401
                       TIMING_LOG, TRACE_LOG, execute, lane_sharding,
                       last_active_ticks, last_plan, last_spans,
                       last_timing, last_trace, resume, span)
from .faults import (ENV_FAULTS, ExecError, FaultInjector,  # noqa: F401
                     FaultSpec, SimulatedCrash, SimulatedOOM)
from .planner import (DEFAULT_MEM_FRACTION, DEFAULT_PIPELINE_DEPTH,  # noqa: F401
                      ENV_BUDGET, ExecPlan, RetryPolicy,
                      auto_budget_bytes, device_free_bytes,
                      host_available_bytes, plan)
from .store import BENCH_FILENAME, TRAJECTORY_CAP, RunStore  # noqa: F401
