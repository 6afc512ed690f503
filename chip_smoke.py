#!/usr/bin/env python3
"""Drive the simulator's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: phases device, kernel,
                                      # main, golden
    python chip_smoke.py --chips 4    # only the lane-sharded sweep, on four
                                      # chips against one

Phases on one chip:

* device -- JAX's first device must be a TPU. There is no CPU fallback.
* kernel -- `resolve_impl("auto")` picks the compiled Pallas kernel, and
  `bfc_fused` on the chip equals its jnp oracle bit for bit on seeded
  random switch states (DRR and SRF, 384 and a ragged 98 ports, 32 queues).
* main -- registry scenario `fig6_incast` (Google workload, load 0.55, 5 %
  incast, seed 9) at the paper's fabric (128 servers, 8 ToRs, 8 spines,
  100 G links, 1 us wires), 4000 flows, the 100-to-1 incast and the
  20 000-tick drain, through `scenarios.run` on one device. BFC and DCQCN
  run on the lax decision path, then BFC again on the Pallas path, whose
  final state and emits must equal the lax lane's bit for bit. The Pallas
  program must hold a `tpu_custom_call`, the budget must come from the
  device's `memory_stats`, and the OOM retry must not fire. Not every
  flow can finish: the heaviest destination of seed 9 needs until tick
  81 423 at line rate (its flows' sizes summed from each arrival on),
  past the 52 685 ticks the run has, so each lane prints how many did.
* golden -- every `config.PRESETS` family re-runs the pinned golden
  micro-case on the Pallas path and is diffed (`replay diff --expect
  same`) against its committed CPU fixture.

`--chips 4` runs one phase, sharded: eight seeds of BFC on the same
paper-scale `fig6_incast` for its first 2048 ticks, lanes sharded over
four chips on the Pallas path, then the same lanes on one chip on the lax
path; the two must be bit-identical.

Each phase prints its compile count and compile seconds (from JAX's
monitoring events; `cache hits` are loads from the persistent cache, see
`repro.compile_cache`). The last line of standard output is one JSON
object, printed only when every phase passed; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time
import traceback
from dataclasses import replace
from pathlib import Path

HERE = Path(__file__).resolve().parent

# An ambient variable must not turn the run into an interpret-mode run, an
# injected-fault run or a differently chunked one.
AMBIENT_VARS = ("REPRO_KERNEL", "REPRO_KERNEL_INTERPRET", "REPRO_FAULTS",
                "REPRO_EXEC_MAX_BYTES")

# The paper's fabric and scale (benchmarks/common.py under BENCH_FULL=1).
PAPER_CLOS = dict(n_servers=128, n_tor=8, n_spine=8)
N_FLOWS = 4000
DRAIN = 20_000
PAPER_INCAST = dict(incast_degree=100, incast_total_kb=20480)
SHARDED_SEEDS = tuple(range(9, 17))
# The sharded phase simulates the first 2048 ticks only: a lane-tick costs
# about 3.7 ms on a v5e and lanes of one chip run one after another, so the
# one-chip side of the comparison would take 8 x 52 685 x 3.7 ms (26 min)
# over the whole horizon.
SHARDED_TICKS = 2048
KERNEL_SEED = 20190923

# What the chip must show; the CPU rehearsal of these phases swaps them.
KERNEL_IMPL = "pallas"
BUDGET_SOURCE = "memory_stats"
KERNEL_MARK = "tpu_custom_call"


class Failed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise Failed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """Counts XLA compiles (persistent-cache loads included) and their
    seconds through JAX's monitoring events."""

    def __init__(self):
        self.n = 0
        self.secs = 0.0
        self.hits = 0

    def on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self


def run_phase(name: str, fn, meter: CompileMeter, failures: list) -> None:
    n0, s0, h0 = meter.n, meter.secs, meter.hits
    t0 = time.perf_counter()
    try:
        fn()
        status = "ok"
    except Exception as err:             # noqa: BLE001 — reported, fails run
        traceback.print_exc()
        failures.append(name)
        status = f"FAIL: {err}"
    log(f"phase {name}: {status} | {meter.n - n0} compiles "
        f"({meter.hits - h0} cache hits) {meter.secs - s0:.1f} s compile, "
        f"{time.perf_counter() - t0:.1f} s wall")


def import_repro() -> None:
    """Import the program from beside this script, never from elsewhere."""
    if not (HERE / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no src/repro beside {__file__}; "
                         "run it from a checkout of the repository")
    sys.path.insert(0, str(HERE / "src"))
    import repro
    if HERE not in Path(repro.__file__).resolve().parents:
        raise SystemExit(f"chip_smoke: imported repro from "
                         f"{repro.__file__}, not from {HERE / 'src'}")


def phase_device(chips: int) -> None:
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's first device "
                         f"is on platform {platform!r} "
                         f"({devs[0].device_kind}); there is no CPU "
                         "fallback")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX sees {len(devs)}")
    log(f"device: {len(devs)} x {devs[0].device_kind} ({platform}), "
        f"jax {jax.__version__}")


# ---- kernel -------------------------------------------------------------------

def phase_kernel() -> None:
    import numpy as np

    from repro.kernels.bfc_step import ops
    from repro.kernels.bfc_step.ref import BIG
    from repro.sim.config import TimingParams

    impl = ops.resolve_impl("auto")
    check(impl == KERNEL_IMPL,
          f"resolve_impl('auto') gave {impl!r}, not {KERNEL_IMPL!r}")
    names = ("n_active", "th", "pause", "sel", "can_tx", "occ_after")
    rng = np.random.default_rng(KERNEL_SEED)
    q = 32
    for scheduler in ("drr", "srf"):
        for p in (384, 98):
            occ = np.where(rng.random((p, q)) < 0.4,
                           rng.integers(1, 80, (p, q)), 0).astype(np.int32)
            qpaused = rng.random((p, q)) < 0.2
            ptr = rng.integers(0, q, p).astype(np.int32)
            blocked = rng.random(p) < 0.1
            key = (rng.integers(0, BIG + 1, (p, q)).astype(np.int32)
                   if scheduler == "srf" else None)
            kw = dict(pause_window=TimingParams().pause_window,
                      scheduler=scheduler, srf_key=key)
            got = ops.fused(occ, qpaused, ptr, blocked, impl=KERNEL_IMPL,
                            **kw)
            want = ops.fused(occ, qpaused, ptr, blocked, impl="ref", **kw)
            for name, g, w in zip(names, got, want):
                check(np.array_equal(np.asarray(g), np.asarray(w)),
                      f"bfc_fused {scheduler} P={p}: {name} differs from "
                      "bfc_fused_ref")
            can_tx = np.asarray(got[4])
            log(f"kernel {scheduler} P={p} Q={q}: bit-identical to "
                f"bfc_fused_ref ({int(can_tx.sum())}/{p} ports transmit)")


# ---- main ---------------------------------------------------------------------

def paper_scenario(**overrides):
    from repro.sim import scenarios
    return replace(scenarios.get("fig6_incast"), **PAPER_INCAST, **overrides)


def run_scenario(sc, impl: str, devices, n_ticks=None) -> list:
    """`scenarios.run` at paper scale on `devices`, every lane on decision
    path `impl` (for `n_ticks` ticks, default the whole horizon); checks
    the execution record and prints one line per lane."""
    import numpy as np

    from repro.kernels.bfc_step import ops
    from repro.sim import scenarios
    from repro.sim.exec import dispatch
    from repro.sim.topology import ClosParams

    rmark = dispatch.RETRY_LOG.mark()
    amark = dispatch.ACTIVE_LOG.mark()
    tmark = dispatch.TIMING_LOG.mark()
    with ops.forced(impl):
        results = scenarios.run(sc, clos=ClosParams(**PAPER_CLOS),
                                n_flows=N_FLOWS, drain=DRAIN,
                                devices=devices, n_ticks=n_ticks)
    retries = dispatch.RETRY_LOG.since(rmark)
    check(not retries, f"the OOM retry fired: {retries}")
    timing = {t["tag"]: t for t in dispatch.TIMING_LOG.since(tmark)}
    active = {tag: a for tag, a in dispatch.ACTIVE_LOG.since(amark)}
    seen: dict = {}
    for r in results:
        t = timing[r.proto]
        k = seen[r.proto] = seen.get(r.proto, -1) + 1
        done = np.asarray(r.state.done)
        m = r.metrics
        log(f"lane {r.label} [{t['kernel_impl']}]: p99 slowdown "
            f"{m.fct_slowdown_p99!r}, avg slowdown {m.fct_slowdown_avg!r}, "
            f"drops {m.drops}, active ticks {int(active[r.proto][k])}/"
            f"{t['n_ticks']}, flows done {int((done >= 0).sum())}/"
            f"{done.size}, wall {t['wall_s']!r} s for its "
            f"{t['lanes']}-lane group on {t['devices']} device(s) "
            f"(compile included)")
        check(t["kernel_impl"] == impl,
              f"{r.label} ran on {t['kernel_impl']!r}, not {impl!r}")
        check(t["budget_source"] == BUDGET_SOURCE,
              f"{r.label} budget came from {t['budget_source']!r}, not "
              f"{BUDGET_SOURCE!r}")
    return results


def same_lane(a, b) -> str:
    """'' when two CaseResults hold bit-identical final state and emits,
    else the first leaf that differs."""
    import numpy as np
    for name in a.state._fields:
        x, y = np.asarray(getattr(a.state, name)), np.asarray(
            getattr(b.state, name))
        if x.shape != y.shape or not np.array_equal(x, y):
            return f"SimState.{name}"
    if not np.array_equal(a.emits, b.emits):
        return "emits"
    return ""


def program_text(result, impl: str) -> str:
    """Compiled text of the batched program `result`'s lane ran in (the
    most recent plan, which covered that lane's group)."""
    from repro.kernels.bfc_step import ops
    from repro.sim import engine, sweep, topology
    from repro.sim.exec import dispatch
    plan = dispatch.LAST_PLAN
    cfg = result.cfg
    with ops.forced(impl):
        go = engine.compiled_runner(
            plan.dims, engine.static_cfg(cfg), plan.f_max, plan.n_ticks,
            plan.unroll, batched=True, segment=plan.segment,
            early_exit=plan.early_exit)
    topo = topology.build(cfg.clos)
    width = plan.chunk_width
    flow_ops = sweep.stack_operands([result.flows] * width, cfg, plan.f_max)
    topo_ops = sweep.stack_topos([topo] * width, cfg, plan.dims)
    return go.lower(flow_ops, topo_ops).compile().as_text()


def phase_main() -> None:
    import jax
    one = jax.devices()[:1]
    sc = paper_scenario(protos=("bfc", "dcqcn"))
    lax = run_scenario(sc, "lax", one)
    kern = run_scenario(replace(sc, protos=("bfc",)), KERNEL_IMPL, one)
    check(KERNEL_MARK in program_text(kern[0], KERNEL_IMPL),
          f"the {KERNEL_IMPL} program holds no {KERNEL_MARK}")
    lax_bfc = next(r for r in lax if r.proto == "bfc")
    diff = same_lane(lax_bfc, kern[0])
    check(not diff, f"bfc on {KERNEL_IMPL} differs from lax at {diff}")
    log(f"main: bfc on {KERNEL_IMPL} is bit-identical to lax (final "
        f"SimState and emits); its program holds a {KERNEL_MARK}")


# ---- golden -------------------------------------------------------------------

def divergence(report: str) -> str:
    """First divergent tick and channel from a `replay diff` report."""
    lines = [ln.strip() for ln in report.splitlines()]
    first = next((ln for ln in lines if ln.startswith("first divergence")),
                 "diverges")
    chan = next((ln for ln in lines if " diverges at tick " in ln), "")
    return f"{first}; {chan}" if chan else first


def phase_golden() -> None:
    import jax

    from repro.kernels.bfc_step import ops
    from repro.sim import sweep
    from repro.sim.config import PRESETS
    from repro.sim.exec import dispatch
    from repro.sim.exec.store import RunStore
    from repro.sim.trace import golden
    from repro.sim.trace.replay import main as replay_main

    topo, flows = golden.golden_case()
    failed = []
    with tempfile.TemporaryDirectory() as root, ops.forced(KERNEL_IMPL):
        store = RunStore(root)
        for name in sorted(PRESETS):
            rmark = dispatch.RETRY_LOG.mark()
            sweep.run_batch(topo, [flows], golden.golden_cfg(PRESETS[name]),
                            golden.GOLDEN_N_TICKS, store=store,
                            devices=jax.devices()[:1])
            check(not dispatch.RETRY_LOG.since(rmark),
                  f"golden {name}: the OOM retry fired")
            impl = dispatch.LAST_TIMING["kernel_impl"]
            check(impl == KERNEL_IMPL, f"golden {name} ran on {impl!r}")
            golden.materialize(store, f"golden_{name}",
                               golden.load_fixture(golden.fixture_path(name)))
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                rc = replay_main(["diff", root, f"golden_{name}", name,
                                  "--expect", "same"])
            if rc == 0:
                log(f"golden {name}: same")
            else:
                log(f"golden {name}: {divergence(out.getvalue())}")
                failed.append(name)
    check(not failed, f"golden traces diverge from their CPU fixtures: "
          f"{failed}")


# ---- sharded ------------------------------------------------------------------

def phase_sharded(chips: int) -> None:
    import jax
    from repro.sim.exec import dispatch
    devs = jax.devices()
    sc = paper_scenario(protos=("bfc",), seeds=SHARDED_SEEDS)
    wide = run_scenario(sc, KERNEL_IMPL, devs[:chips], SHARDED_TICKS)
    plan, timing = dispatch.LAST_PLAN, dispatch.LAST_TIMING
    check(plan.n_devices == chips,
          f"plan placed lanes on {plan.n_devices} devices, not {chips}")
    check(timing["out_devices"] == chips,
          f"outputs spanned {timing['out_devices']} devices, not {chips}")
    narrow = run_scenario(sc, "lax", devs[:1], SHARDED_TICKS)
    for a, b in zip(wide, narrow):
        diff = same_lane(a, b)
        check(not diff, f"{a.label}: {chips}-chip run differs from the "
              f"1-chip run at {diff}")
    log(f"sharded: {len(wide)} lanes on {chips} chips on {KERNEL_IMPL} "
        f"({plan.chunk_width} lanes per chunk, {plan.lanes_per_device} per "
        "device) are bit-identical to the same lanes on one chip on lax")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded sweep on four chips")
    args = ap.parse_args(argv)
    for var in AMBIENT_VARS:
        os.environ.pop(var, None)

    import_repro()
    import jax
    from repro import compile_cache
    phase_device(args.chips)
    log(f"compile cache: {compile_cache.enable()}")
    meter = CompileMeter().install()

    failures: list = []
    if args.chips == 1:
        run_phase("kernel", phase_kernel, meter, failures)
        run_phase("main", phase_main, meter, failures)
        run_phase("golden", phase_golden, meter, failures)
    else:
        run_phase("sharded", lambda: phase_sharded(args.chips), meter,
                  failures)
    log(f"total: {meter.n} compiles ({meter.hits} cache hits), "
        f"{meter.secs:.1f} s compile")
    if failures:
        print(f"chip_smoke: failed phases: {failures}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
