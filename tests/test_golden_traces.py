"""Golden-trace regression fixtures: every protocol family, re-run on the
pinned micro-case, must reproduce its committed trace bit-for-bit.

The fixtures under tests/fixtures/traces/ (one per `config.PRESETS`
family, written by scripts/gen_golden_traces.py) pin each family's full
per-tick channel trace on a tiny Clos + uniform+incast workload. This
test re-runs each family fresh, materializes its fixture into the same
RunStore as a synthetic spooled run, and asserts the stock replay CLI's
``diff --expect same`` verdict — so any unintended behavioural drift in
any phase law surfaces as a first-divergence tick, not a silent metrics
shift. The same holds for every pinned case of `golden.CASES`: BFC on a
k = 4 fat-tree (6-hop routes) under tests/fixtures/traces_fat_tree/ too.
Also pins: the check-mode CLI (structural freshness, orphan and
meta-drift detection), corruption detection (a perturbed fixture must
fail the diff), and the ``python -m repro.sim.replay`` subprocess
entry point."""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.sim import sweep
from repro.sim.config import PRESETS
from repro.sim.exec.store import RunStore
from repro.sim.trace import golden
from repro.sim.trace.replay import main as replay_main

FAMILIES = sorted(PRESETS)
# (case, family) of every fixture; the leaf-spine's keep the family's name
PINNED = [pytest.param(case, name, id=name if case == golden.DEFAULT_CASE
                       else f"{case}-{name}")
          for case in golden.CASES for name in golden.families(case)]
REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def rerun_store(tmp_path_factory):
    """Per pinned case, one RunStore holding, per family, a fresh traced
    re-run (tag ``<name>``) and its committed fixture (tag
    ``golden_<name>``)."""
    roots = {}
    for case in golden.CASES:
        roots[case] = tmp_path_factory.mktemp(f"golden_rerun_{case}")
        store = RunStore(roots[case])
        topo, flows = golden.golden_case(case)
        for name in golden.families(case):
            sweep.run_batch(topo, [flows],
                            golden.golden_cfg(PRESETS[name], case),
                            golden.GOLDEN_N_TICKS, store=store)
            golden.materialize(store, f"golden_{name}", golden.load_fixture(
                golden.fixture_path(name, case=case)))
    return roots


def test_fixtures_structurally_fresh():
    assert golden.check_fixtures() == []


@pytest.mark.parametrize("case,name", PINNED)
def test_family_reproduces_golden_trace(rerun_store, case, name):
    """replay diff --expect same: the CI regression contract per family."""
    assert replay_main(["diff", str(rerun_store[case]),
                        f"golden_{name}", name, "--expect", "same"]) == 0


@pytest.mark.parametrize("case", list(golden.CASES))
def test_corrupted_fixture_fails_diff(rerun_store, capsys, case):
    """The guard actually guards: a single flipped channel value must turn
    the --expect same verdict into a non-zero exit."""
    store = RunStore(rerun_store[case])
    fx = golden.load_fixture(golden.fixture_path("bfc", case=case))
    fx["trace"] = fx["trace"].copy()
    fx["trace"][0, 100, 0] += 1
    golden.materialize(store, "golden_bfc_corrupt", fx)
    assert replay_main(["diff", str(rerun_store[case]), "golden_bfc_corrupt",
                        "bfc", "--expect", "same"]) == 1
    out = capsys.readouterr().out
    assert "first divergence at tick 100" in out


def test_fat_tree_case_runs_six_hop_routes():
    topo, flows = golden.golden_case("fat_tree")
    assert (topo.n_servers, topo.n_switches, topo.hops) == (16, 20, 6)
    assert ((flows.routes >= 0).sum(1) == 6).any()
    meta = golden.pinned_meta("fat_tree")
    assert meta["clos"]["k"] == 4 and meta != golden.pinned_meta()


def test_fat_tree_fixture_pauses():
    """The fat-tree case exercises the backpressure it pins: BFC's pause
    channel fires there."""
    fx = golden.load_fixture(golden.fixture_path("bfc", case="fat_tree"))
    lay = golden.golden_layout("fat_tree")
    ch = next(c for c in lay.channels if c.name == "paused_q")
    assert np.asarray(fx["trace"])[..., ch.start:ch.start + ch.width].any()


def test_check_covers_every_case(tmp_path):
    """With no case named, the check reports every pinned case's fixtures
    under its own directory of the root."""
    problems = golden.check_fixtures(tmp_path)
    assert len(problems) == len(PINNED)
    for case in golden.CASES:
        assert sum(p.startswith(f"{case}/") for p in problems) \
            == len(golden.families(case))
        assert all(str(tmp_path / golden.CASES[case].directory) in p
                   for p in problems if p.startswith(f"{case}/"))


def test_check_flags_orphans_and_drift(tmp_path):
    """check_fixtures is the cheap CI gate: missing family, orphan file,
    and pinned-meta drift are each reported."""
    problems = golden.check_fixtures(tmp_path, case="leaf_spine")
    assert len(problems) == len(FAMILIES)
    assert all("missing fixture" in p for p in problems)
    fx = golden.load_fixture(golden.fixture_path("bfc"))
    stale = dict(fx, meta={**fx["meta"], "n_ticks": 1})
    golden.save_fixture(golden.fixture_path("bfc", tmp_path), stale)
    (tmp_path / "traces" / "not_a_family.npz").write_bytes(b"junk")
    problems = golden.check_fixtures(tmp_path, case="leaf_spine")
    assert any("meta drifted" in p for p in problems)
    assert any("orphan fixture" in p for p in problems)


def test_replay_cli_subprocess(rerun_store):
    """The committed contract runs outside pytest too: the module CLI
    (python -m repro.sim.replay) delivers the same verdict."""
    env_path = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.sim.replay", "diff",
         str(rerun_store["leaf_spine"]), "golden_sfc", "sfc", "--expect",
         "same"],
        capture_output=True, text=True,
        env={"PYTHONPATH": env_path, "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "HOME": "/root"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "identical over" in proc.stdout
