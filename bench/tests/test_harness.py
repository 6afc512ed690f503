"""The harness: cells, configurations, mixes and metrics found by name; a
run refused off the chip; a small run on the CPU judged correct; and the
timed path broken underneath judged not correct."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
from conftest import BENCH, ROOT

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    bench, w, config, traffic = harness.resolve(cell)
    assert config["name"] == w["config"]
    assert traffic["name"] == w["traffic"]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    assert entry["file"].startswith(bench["paths"][0] + "/")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    for kind in ("end_to_end", "per_layer"):
        assert harness.metrics_of(bench, w, kind)


@pytest.mark.parametrize("metric", [m["name"]
                                    for m in BENCHMARK["per_layer"]])
def test_metric_has_reader(metric):
    assert callable(harness.reader(metric))


def test_new_cell_from_files_alone(tmp_path):
    """A cell added as a traffic file and one BENCHMARK.json entry is
    found without an edit to the harness."""
    shutil.copytree(BENCH / "traffic", tmp_path / "bench" / "traffic")
    shutil.copytree(BENCH / "configs", tmp_path / "bench" / "configs")
    mix = json.loads((BENCH / "traffic" / "fig6_x1.json").read_text())
    mix.update(name="fig6_x2", lanes=2)
    (tmp_path / "bench" / "traffic" / "fig6_x2.json").write_text(
        json.dumps(mix))
    bench = dict(BENCHMARK)
    bench["workloads"] = BENCHMARK["workloads"] + [{
        "name": "bfc_paper.fig6_x2", "config": "bfc_paper",
        "traffic": "fig6_x2", "chips": 1, "why": "two lanes"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    _, cell, config, traffic = harness.resolve("bfc_paper.fig6_x2",
                                               root=tmp_path)
    assert traffic["lanes"] == 2 and config["name"] == "bfc_paper"
    per_layer = {m["name"] for m in harness.metrics_of(bench, cell,
                                                       "per_layer")}
    assert "device.idle_frac" in per_layer


RECORDING_MODULE = """\"\"\"The leaf-spine, each function recording that it was called.\"\"\"
from pathlib import Path

import leaf_spine

CALLS = Path(__file__).with_name("calls.txt")


def _recorded(name):
    def call(*args, **kwargs):
        with open(CALLS, "a") as fh:
            fh.write(name + "\\n")
        return getattr(leaf_spine, name)(*args, **kwargs)
    return call


fabric, program, generate, simulate, summarize = map(
    _recorded, ("fabric", "program", "generate", "simulate", "summarize"))
"""


def test_new_configuration_from_files_alone(tmp_path):
    """A configuration added as a configuration file naming its module,
    the module, a traffic file and its BENCHMARK.json entries runs through
    `harness.run` in a copy of the tree, with the harness's files as they
    are, and is judged correct."""
    from conftest import small_config, small_traffic
    ignore = shutil.ignore_patterns(".work", "__pycache__", ".jax_cache")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=ignore)
    shutil.copytree(ROOT / "src", tmp_path / "src", ignore=ignore)
    config = dict(small_config("bfc_paper"), name="recorded_leaf_spine",
                  module="recorded_leaf_spine.py")
    (tmp_path / "bench" / "configs" / "recorded_leaf_spine.json").write_text(
        json.dumps(config))
    (tmp_path / "bench" / "recorded_leaf_spine.py").write_text(
        RECORDING_MODULE)
    (tmp_path / "bench" / "traffic" / "fig6_small.json").write_text(
        json.dumps({**small_traffic("fig6_x1", n_ticks=200),
                    "name": "fig6_small"}))
    bench = dict(BENCHMARK)
    bench["configs"] = BENCHMARK["configs"] + [{
        "name": "recorded_leaf_spine", "source": config["source"],
        "file": "bench/configs/recorded_leaf_spine.json",
        "reduced": config["reduced"], "why": "a module of its own"}]
    bench["workloads"] = BENCHMARK["workloads"] + [{
        "name": "recorded_leaf_spine.fig6_small",
        "config": "recorded_leaf_spine", "traffic": "fig6_small",
        "chips": 1, "why": "files alone"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    script = """
import sys, jax
sys.path.insert(0, "bench")
import harness
harness.check_device = lambda chips, peaks: jax.devices()[:chips]
sys.exit(harness.run(["--workload", "recorded_leaf_spine.fig6_small",
                      "--seed", "4294967311", "--seconds", "0.2"]))
"""
    out = subprocess.run([sys.executable, "-c", script], cwd=tmp_path,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["check"]["mismatch"]["value"] == 0
    calls = set((tmp_path / "bench" / "calls.txt").read_text().split())
    assert calls == set(harness.MODULE_FUNCTIONS)
    for name in ("harness.py", "flowgen.py", "reference.py", "check.py",
                 "limits.py", "leaf_spine.py"):
        assert ((tmp_path / "bench" / name).read_bytes()
                == (BENCH / name).read_bytes()), name


@pytest.mark.parametrize("module, why", [
    ("tests/data/no_such_module.py", "missing"),
    ("tests/data/partial_module.py", "lacks summarize"),
    ("../src/repro/__init__.py", "outside"),
])
def test_incomplete_module_is_refused(module, why, small_cell, capsys):
    """A named module that is missing, lacks one of the five functions or
    lies outside the benchmark's directory is refused with no result
    line."""
    _, _, config, _ = small_cell("bfc_paper.fig6_x1")
    config["module"] = module
    with pytest.raises(harness.Refused):
        harness.run(["--workload", "bfc_paper.fig6_x1", "--seed", "3",
                     "--seconds", "0.1"])
    out = capsys.readouterr()
    assert "{" not in out.out and why in out.err


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_refuses_on_cpu():
    out = _run_cli(ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    out = _run_cli(tmp_path)
    assert out.returncode != 0
    assert "{" not in out.stdout


def _result(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1])


SMALL = {"bfc_paper.fig6_x1": {}, "dcqcn_paper.fig6_x1": {},
         "bfc_paper.fig6_x8": {"n_ticks": 150}}


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_small_run_is_correct(cell, small_cell, capsys):
    small_cell(cell, **SMALL[cell])
    assert harness.run(["--workload", cell, "--seed", str(2**31 + 99),
                        "--seconds", "0.5"]) == 0
    res = _result(capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"lane_ticks_per_s", "setup_s"}
    assert list(res)[-1] == "check"
    assert res["check"]["mismatch"]["value"] == 0


FOUR_CHIP = "bfc_paper.fig6_x8_4chip"


def _run_four_chips(cwd, fault: str = ""):
    """A small run of the four-chip cell on four virtual CPU devices, with
    `fault` (a planting function of this module) applied first."""
    script = f"""
import sys
sys.path[:0] = [{str(BENCH)!r}, {str(BENCH / 'tests')!r}]
import jax, pytest, conftest, harness
bench, cell, config, traffic = harness.resolve({FOUR_CHIP!r})
config = conftest.small_config(cell['config'])
traffic = conftest.small_traffic(cell['traffic'], n_ticks=120)
harness.resolve = lambda name: (bench, cell, config, traffic)
harness.check_device = lambda chips, peaks: jax.devices()[:chips]
assert cell['chips'] == len(jax.devices()) == 4
if {fault!r}:
    import test_harness
    getattr(test_harness, {fault!r})(pytest.MonkeyPatch())
sys.exit(harness.run(['--workload', cell['name'], '--seed', '5',
                      '--seconds', '0.1']))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=cwd)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    return res


def test_four_chip_cell_on_virtual_devices(tmp_path):
    """The four-chip cell's lane-sharded path (shard_map over `lanes`, two
    lanes a device) on four virtual CPU devices."""
    res = _run_four_chips(tmp_path)
    assert res["correct"] is True and res["failed"] == 0


# ---- faults planted under the timed path ------------------------------------

@pytest.fixture
def fresh_programs():
    from repro.sim import engine
    engine._compiled_runner.cache_clear()
    yield
    engine._compiled_runner.cache_clear()


def _frozen_step(monkeypatch):
    """A step that returns its state unchanged."""
    from repro.sim import engine
    make_step = engine.make_step

    def frozen(*a, **k):
        init, step = make_step(*a, **k)

        def same(st, ops, topo):
            return st, step(st, ops, topo)[1]
        return init, same
    monkeypatch.setattr(engine, "make_step", frozen)


def _altered_token(monkeypatch):
    """One flow's delivered count altered where the step produces it."""
    from repro.sim import phases
    stats = phases.stats

    def altered(env, st, ops, topo, ctx):
        new_st, emit = stats(env, st, ops, topo, ctx)
        bump = (new_st.t == 50).astype(new_st.delivered.dtype)
        return new_st._replace(
            delivered=new_st.delivered.at[0].add(bump)), emit
    monkeypatch.setattr(phases, "stats", altered)


def _half_batch(monkeypatch):
    """Half of the lanes left out: their state and emits never computed."""
    import numpy as np
    from repro.sim import sweep
    run_batch = sweep.run_batch

    def half(topo, flowsets, *a, **k):
        st, emits = run_batch(topo, flowsets, *a, **k)
        h = len(flowsets) // 2
        st = type(st)(*[np.concatenate([np.asarray(x)[:h],
                                        np.zeros_like(np.asarray(x)[h:])])
                        for x in st])
        emits = np.concatenate([np.asarray(emits)[:h],
                                np.zeros_like(np.asarray(emits)[h:])])
        return st, emits
    monkeypatch.setattr(sweep, "run_batch", half)


def _one_shard_for_all(monkeypatch):
    """The gather from the chips left out: every lane read back from the
    first device's shard."""
    import numpy as np
    from repro.sim.exec import dispatch
    land = dispatch._land

    def one_shard(st, emits, active, n_real):
        per = len(emits.addressable_shards[0].data)
        st, emits, active = land(st, emits, active, n_real)

        def first(x):
            return np.resize(np.asarray(x)[:per], np.shape(x))
        return (type(st)(*map(first, st)), first(emits), first(active))
    monkeypatch.setattr(dispatch, "_land", one_shard)


@pytest.mark.parametrize("fault", ["_frozen_step", "_half_batch",
                                   "_one_shard_for_all", "_altered_token"])
def test_four_chip_fault_is_not_correct(fault, tmp_path):
    res = _run_four_chips(tmp_path, fault)
    assert res["correct"] is False
    assert res["check"]["mismatch"]["value"] > 0


@pytest.mark.parametrize("cell, fault", [
    ("bfc_paper.fig6_x1", _frozen_step),
    ("dcqcn_paper.fig6_x1", _frozen_step),
    ("bfc_paper.fig6_x1", _altered_token),
    ("dcqcn_paper.fig6_x1", _altered_token),
    ("bfc_paper.fig6_x8", _half_batch),
])
def test_fault_is_not_correct(cell, fault, small_cell, monkeypatch, capsys,
                              fresh_programs):
    small_cell(cell, **SMALL[cell])
    fault(monkeypatch)
    assert harness.run(["--workload", cell, "--seed", "7",
                        "--seconds", "0.1"]) == 0
    res = _result(capsys)
    assert res["correct"] is False
    assert res["check"]["mismatch"]["value"] > 0
