"""One benchmark run of one cell, driven by the data in BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

1. Resolve the cell's configuration (`configs/<config>.json`) and traffic
   mix (`traffic/<traffic>.json`) by name.
2. Load the configuration's module (`leaf_spine.py` unless the file
   names another under `module`): it builds the fabric, the program's
   case and each lane's flows, and runs the reference.
3. Generate each lane's flows with the module's generator; lane i gets
   seed n + i.
4. Turn on the program's persistent compilation cache (a fixed directory
   in the checkout, or $JAX_COMPILATION_CACHE_DIR) and make one warm call.
   Everything up to here is set-up.
5. The window: a closed loop with one caller. Each call is
   `sweep.run_grid(topo, cases, n_ticks=H)` with the same flows, timed
   from entry to its return with results on the host; the next starts
   when it returns, until `--seconds` have passed. A compile inside the
   window fails the run. With `--trace 1` the first window call runs
   under the profiler and the per-layer metrics are read from its trace
   by the readers in `metrics/`, one file per metric.

Then the outputs are checked (`check.py` against the module's
reference), and the last line of standard output is one JSON object. On
a machine whose first device is not a TPU in `peaks.json`, or with fewer
chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import multiprocessing as mp
import os
import shutil
import sys
import time
import types
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# An ambient variable must not change the decision path, inject faults or
# change how lanes are chunked: those come from the configuration file.
AMBIENT_VARS = ("REPRO_KERNEL", "REPRO_KERNEL_INTERPRET", "REPRO_FAULTS",
                "REPRO_EXEC_MAX_BYTES")
TRACE_DIR = HERE / ".work" / "trace"
DEFAULT_MODULE = "leaf_spine.py"
MODULE_FUNCTIONS = ("fabric", "program", "generate", "simulate", "summarize")
# float_gap's limit: above the largest reading of sound runs, below the
# smallest of the bfloat16 control (PERF.md, "How correct is decided")
FLOAT_GAP_LIMIT = 1e-4


class Refused(SystemExit):
    """Exit without a result: wrong machine, missing file, bad input."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


class CompileMeter:
    """Counts XLA backend compiles (persistent-cache loads included) and
    their seconds through JAX's monitoring events."""

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


def load_json(path: Path) -> dict:
    try:
        with open(path) as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise Refused(f"missing {path}") from None


def resolve(cell_name: str, root: Path = ROOT):
    """(benchmark, cell, configuration document, traffic document)."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise Refused(f"no workload {cell_name!r} in BENCHMARK.json")
    cell = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / HERE.relative_to(ROOT) / "traffic"
                        / f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def load_module(path: Path, name: str):
    """Execute the Python file at `path` as a module named `name`."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise Refused(f"missing {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The `read(ctx)` function of a per-layer metric's own file."""
    return load_module(HERE / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric.replace('.', '_')}").read


def module_of(config: dict):
    """The module that builds a configuration's fabric, program case and
    flows and runs its reference: the file its `module` key names,
    relative to `bench/`, else the leaf-spine (`leaf_spine.py`)."""
    path = (HERE / config.get("module", DEFAULT_MODULE)).resolve()
    if HERE.resolve() not in path.parents:
        raise Refused(f"module {path} lies outside {HERE}")
    mod = load_module(path, f"bench_config_{path.stem}")
    missing = [f for f in MODULE_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise Refused(f"module {path} lacks {', '.join(missing)}")
    return mod


def metrics_of(bench: dict, cell: dict, kind: str) -> list:
    """The metrics of `kind` ('end_to_end' or 'per_layer') this cell
    reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def import_program(root: Path = ROOT):
    """Import the system under test from the checkout's `src/`."""
    if not (root / "src" / "repro").is_dir():
        raise Refused(f"no src/repro in {root}: run from a checkout")
    sys.path.insert(0, str(root / "src"))
    import repro
    if root not in Path(repro.__file__).resolve().parents:
        raise Refused(f"imported repro from {repro.__file__}")


def check_device(chips: int, peaks: dict):
    import jax
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu" or kind not in peaks["devices"]:
        raise Refused(f"needs a TPU from peaks.json, found "
                      f"{devs[0].platform} {kind!r}; no CPU fallback")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def build_cases(config: dict, traffic: dict, seed: int):
    """(topology, cases, per-lane flow dicts) for one run."""
    import flowgen
    from repro.sim.workload import FlowSet
    mod = module_of(config)
    cfg, topo = mod.program(config)
    fabric = mod.fabric(config)
    flows = [mod.generate(fabric, traffic, seed + i)
             for i in range(traffic["lanes"])]
    cases = [(f"{config['name']}/{traffic['name']}/lane{i}", cfg,
              FlowSet(**{k: f[k] for k in flowgen.ARRAYS},
                      horizon=f["horizon"]))
             for i, f in enumerate(flows)]
    return topo, cases, flows


def float_rules(device_kind: str) -> dict:
    rules = load_json(HERE / "float_rules.json")["devices"]
    if device_kind not in rules:
        raise Refused(f"no float rules for {device_kind!r}")
    return rules[device_kind]


def _reference_lane(job):
    config, flows, n_ticks, rules, fdtype = job
    mod = module_of(config)
    fabric = mod.fabric(config)
    st, emits = mod.simulate(fabric, config, flows, n_ticks, rules, fdtype)
    return st, emits, mod.summarize(st, emits, flows, fabric.n_ports)


def references(config, lane_flows, n_ticks, rules, fdtype=np.float32):
    """The configuration's reference run over each lane's flows for
    `n_ticks[i]` ticks: a list of (final state, emits, summary). Lanes run
    in processes of their own, which import nothing of the program."""
    jobs = [(config, f, n, rules, fdtype)
            for f, n in zip(lane_flows, n_ticks)]
    if len(jobs) < 2:
        return [_reference_lane(j) for j in jobs]
    with ProcessPoolExecutor(min(len(jobs), os.cpu_count() or 1),
                             mp_context=mp.get_context("spawn")) as pool:
        return list(pool.map(_reference_lane, jobs))


def judge(results, flows, lanes, config, rules, fdtype=np.float32):
    """(mismatch, float_gap, details) of `lanes` of one call's results
    against the configuration's reference."""
    import check
    refs = references(config, [flows[k] for k in lanes],
                      [int(np.shape(results[k].emits)[0]) for k in lanes],
                      rules, fdtype)
    mismatch, gap, details = 0, 0.0, {}
    for k, (st, emits, ref_m) in zip(lanes, refs):
        r = results[k]
        mm, g, det = check.compare_lane(r.state, r.emits, r.metrics, st,
                                        emits, ref_m)
        mismatch += mm
        gap = max(gap, g)
        if det:
            details[f"lane{k}"] = det
    return mismatch, gap, details


def check_lines(numbers: dict) -> list:
    return [f"check {name} {v['value']!r} limit {v['limit']!r}"
            for name, v in numbers.items()]


def run(argv=None, t0: float = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="one benchmark run of a cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise Refused("--seed must be a whole number >= 0")
    for var in AMBIENT_VARS:
        os.environ.pop(var, None)
    # the TPU runtime would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    bench, cell, config, traffic = resolve(args.workload)
    peaks = load_json(HERE / "peaks.json")
    sys.path.insert(0, str(HERE))
    import_program()
    module_of(config)
    import jax
    devs = check_device(cell["chips"], peaks)
    ready_s = time.perf_counter() - t0
    from repro import compile_cache
    from repro.sim import sweep
    import check
    cache_dir = compile_cache.enable()
    meter = CompileMeter().install()
    used = devs[:cell["chips"]]
    rules = float_rules(devs[0].device_kind)

    t_flows = time.perf_counter()
    topo, cases, flows = build_cases(config, traffic, args.seed)
    H = int(traffic["n_ticks"])
    pad = int(traffic["flows_padded_to"])

    def call():
        return sweep.run_grid(topo, cases, n_ticks=H, pad_multiple=pad,
                              devices=used)

    t_warm = time.perf_counter()
    call()                                   # compile or load, then warm
    setup_s = time.perf_counter() - t0
    compile_setup = (meter.n, meter.secs, meter.hits)
    print(f"setup {setup_s:.3f} s: chip ready at {ready_s:.3f} s, flows "
          f"{t_warm - t_flows:.3f} s, warm call {t0 + setup_s - t_warm:.3f} "
          f"s; {meter.n} compiles ({meter.hits} cache loads), "
          f"{meter.secs:.3f} s compiling; cache {cache_dir}",
          file=sys.stderr, flush=True)

    rng = np.random.default_rng(args.seed)
    walls, digests = [], []
    kept, kept_at = None, 0
    window_t0 = time.perf_counter()
    while True:
        traced = args.trace and not walls
        if traced:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window_call"):
            res = call()
        walls.append(time.perf_counter() - start)
        if traced:
            jax.profiler.stop_trace()
        digests.append(check.digest(res))
        # keep one call's results, drawn uniformly from the seed
        if rng.integers(0, len(walls)) == 0:
            kept, kept_at = res, len(walls) - 1
        del res
        if time.perf_counter() - window_t0 >= args.seconds:
            break
    if meter.n != compile_setup[0]:
        print(f"bench: {meter.n - compile_setup[0]} compiles inside the "
              "window", file=sys.stderr, flush=True)
        return 1
    lanes = len(cases)
    lane_ticks = lanes * H
    memory_peak = max(int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in used)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(used), "memory_peak_bytes": memory_peak}
    out_metrics, notes, breakdown = {}, {}, None
    if args.trace:
        import devtrace
        import switch_decision
        reduction = devtrace.reduce(devtrace.load(str(TRACE_DIR)),
                                    switch_decision.KERNEL,
                                    devices=[d.id for d in used])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = sum(reduction.busy_s.values()) / len(
            reduction.busy_s)
        device["window_s"] = reduction.window_s
        ctx = types.SimpleNamespace(
            reduction=reduction, lane_ticks=lane_ticks, peaks=peaks[
                "devices"][devs[0].device_kind],
            n_ports=topo.n_ports, n_queues=config["proto"]["n_queues"],
            compile_setup_s=compile_setup[1], notes=notes)
        for m in metrics_of(bench, cell, "per_layer"):
            value = reader(m["name"])(ctx)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": [list(x) for x in reduction.device_ops],
                     "idle_gaps": [list(x) for x in reduction.idle_gaps]}
    else:
        measured = {"lane_ticks_per_s": lane_ticks * len(walls) / sum(walls),
                    "setup_s": setup_s}
        for m in metrics_of(bench, cell, "end_to_end"):
            out_metrics[m["name"]] = {"value": measured[m["name"]],
                                      "unit": m["unit"]}

    # the outputs: every call the same, and every lane of one call equal
    # to the reference
    check_at = list(range(lanes))
    mismatch, gap, details = judge(kept, flows, check_at, config, rules)
    differ = sum(d != digests[kept_at] for d in digests)
    numbers = {"mismatch": {"value": mismatch, "limit": 0},
               "float_gap": {"value": gap, "limit": FLOAT_GAP_LIMIT},
               "calls_differ": {"value": differ, "limit": 0}}
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    failed = differ * lanes + (len(details) if details else 0)
    print(f"window {len(walls)} calls of {lanes} lanes x {H} ticks: "
          f"{[round(w, 4) for w in walls]} s; checked call {kept_at} "
          f"lanes {check_at}; {notes}", file=sys.stderr)
    if details:
        print(f"differences from the reference: {details}", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": len(walls) * lanes,
              "failed": int(failed), "metrics": out_metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    print("\n".join(check_lines(numbers)), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
