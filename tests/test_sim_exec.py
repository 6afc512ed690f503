"""The execution layer (`sim/exec`): planner math, budget sources,
multi-device sharded dispatch bit-identity, the double-buffered pipeline,
and the run store.

scripts/ci.sh runs this file in its own pytest process under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the sharded
dispatch path is exercised on CPU; every test here also passes on a plain
single-device run (multi-device-only assertions are guarded)."""
import json

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.sim import engine, sweep, topology, workload
from repro.sim import exec as exec_
from repro.sim.config import BFC, DCTCP, SimConfig
from repro.sim.topology import ClosParams, TopoDims

CLOS = ClosParams(n_servers=8, n_tor=2, n_spine=2, switch_buffer_pkts=512)
N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 2, reason="needs >1 device (ci.sh forces 4 host devices)")


@pytest.fixture(scope="module")
def topo():
    return topology.build(CLOS)


@pytest.fixture(scope="module")
def cfg():
    return SimConfig(proto=BFC, clos=CLOS)


def _flows(topo, seed, n=24):
    wp = workload.WorkloadParams(workload="uniform", load=0.5, seed=seed)
    return workload.generate(topo, wp, n)


def _states_equal(a, b, label=""):
    for name in a._fields:
        assert np.array_equal(np.asarray(getattr(a, name)),
                              np.asarray(getattr(b, name))), \
            f"{label}: SimState.{name} differs"


def _plan(cfg, n_lanes=5, n_ticks=512, **kw):
    dims = TopoDims.of(topology.build(CLOS))
    f_max = 64
    return exec_.plan(dims, cfg, f_max, n_ticks, n_lanes, **kw)


# ---- planner ----------------------------------------------------------------
def test_plan_explicit_budget_floor_division(cfg):
    p = _plan(cfg, budget=None)
    per = p.per_lane_bytes
    assert per > 0 and p.budget_source == "uncapped"
    # uncapped: the whole grid in one chunk (rounded up to a device
    # multiple when sharded)
    assert p.n_chunks == 1 and p.chunk_width >= p.n_lanes
    assert p.chunk_width % p.n_devices == 0

    capped = _plan(cfg, budget=3 * per + per // 2, pipeline_depth=1)
    assert capped.budget_source == "caller"
    # floor(3.5 lanes) -> 3, then down to a device multiple (never over
    # budget); single device keeps the plain floor
    assert capped.chunk_width * per <= 3 * per + per // 2
    if capped.n_devices == 1:
        assert capped.chunk_width == 3

    # the dispatcher keeps pipeline_depth chunks device-resident, so each
    # chunk of a grid that must split gets budget/depth bytes
    halved = _plan(cfg, budget=4 * per, pipeline_depth=2)
    assert halved.chunk_width * per <= 4 * per // 2
    if halved.n_devices == 1:
        assert halved.chunk_width == 2
    # ... but a grid that fits the budget outright stays one chunk (8x
    # headroom also covers the round-up to a device multiple when sharded)
    whole = _plan(cfg, budget=8 * per, pipeline_depth=2)
    assert whole.n_chunks == 1


def test_plan_budget_smaller_than_device_set_shrinks_devices(cfg):
    per = _plan(cfg, budget=None).per_lane_bytes
    p = _plan(cfg, budget=4 * per)  # /depth 2 -> 2 lanes per chunk
    assert p.chunk_width == 2
    assert p.n_devices == min(2, N_DEV)
    assert p.n_chunks == 3          # 5 lanes in chunks of 2


@multi_device
def test_plan_rounds_width_up_to_device_multiple(cfg):
    # 5 lanes, uncapped, D devices -> one padded chunk of ceil-multiple
    p = _plan(cfg, n_lanes=5, budget=None)
    assert p.sharded
    assert p.chunk_width == -(-5 // N_DEV) * N_DEV
    assert p.lanes_per_device * p.n_devices == p.chunk_width


def test_plan_env_budget_wins(cfg, monkeypatch):
    per = _plan(cfg, budget=None).per_lane_bytes
    monkeypatch.setenv(exec_.ENV_BUDGET, str(4 * per))
    p = _plan(cfg, budget="auto")
    assert p.budget_source == "env"
    assert p.budget_bytes == 4 * per


def test_auto_budget_source_fallbacks(cfg, monkeypatch, tmp_path):
    monkeypatch.delenv(exec_.ENV_BUDGET, raising=False)

    class Dev:
        def __init__(self, stats, platform="tpu"):
            self._stats = stats
            self.platform = platform

        def memory_stats(self):
            return self._stats

    # accelerator-style devices report memory_stats; lanes shard evenly,
    # so the least-free device bounds the whole set (min * n, not sum)
    devs = [Dev({"bytes_limit": 1000, "bytes_in_use": 200}),
            Dev({"bytes_limit": 1000, "bytes_in_use": 500})]
    budget, source = exec_.auto_budget_bytes(devs, fraction=1.0)
    assert (budget, source) == (500 * 2, "memory_stats")

    # an accelerator that reports no stats is an error, never host RAM
    meminfo = tmp_path / "meminfo"
    meminfo.write_text("MemTotal:  200 kB\nMemAvailable:  100 kB\n")
    with pytest.raises(RuntimeError, match="reports no memory limit"):
        exec_.auto_budget_bytes([Dev(None)], meminfo=str(meminfo))

    # CPU devices (no stats) budget the host's MemAvailable
    budget, source = exec_.auto_budget_bytes([Dev(None, "cpu")],
                                             fraction=0.5,
                                             meminfo=str(meminfo))
    assert (budget, source) == (100 * 1024 // 2, "host_meminfo")

    # nothing readable -> uncapped
    budget, source = exec_.auto_budget_bytes(
        [Dev(None, "cpu")], meminfo=str(tmp_path / "missing"))
    assert (budget, source) == (None, "uncapped")


def test_host_available_bytes_parses_meminfo():
    got = exec_.host_available_bytes()
    assert got is None or got > 0
    assert exec_.host_available_bytes("/nonexistent/meminfo") is None


# ---- dispatcher -------------------------------------------------------------
def test_execute_bit_identical_to_serial_engine_run(topo, cfg):
    """The planned (sharded when multi-device, chunked, double-buffered)
    path must be bit-identical to unbatched serial `engine.run` — the
    acceptance property, at mini scale."""
    flowsets = [_flows(topo, s) for s in range(5)]
    n_ticks = 512
    st, em = sweep.run_batch(topo, flowsets, cfg, n_ticks)
    plan = exec_.last_plan()
    assert plan.n_lanes == 5
    if N_DEV > 1:
        assert plan.sharded and plan.chunk_width % N_DEV == 0
    for k, fl in enumerate(flowsets):
        st_s, em_s = engine.run(topo, fl, cfg, n_ticks)
        assert np.array_equal(em[k], em_s), f"lane {k} emits"
        _states_equal(sweep.select_config(st, k, fl.n_flows),
                      sweep.trim_state(st_s, fl.n_flows), f"lane {k}")


def test_chunked_sharded_matches_unchunked_one_trace(topo, cfg):
    flowsets = [_flows(topo, s) for s in range(5)]
    n_ticks = 512
    st_full, em_full = sweep.run_batch(topo, flowsets, cfg, n_ticks)
    per = exec_.last_plan().per_lane_bytes
    before = engine.trace_count()
    st_ch, em_ch = sweep.run_batch(topo, flowsets, cfg, n_ticks,
                                   max_batch_bytes=4 * per)
    assert engine.trace_count() - before <= 1, \
        "all chunks of a budget-split grid must share one program"
    assert exec_.last_plan().n_chunks == 3
    assert np.array_equal(em_full, em_ch)
    _states_equal(st_full, st_ch, "chunked")


def test_pipeline_depth_is_inert(topo, cfg):
    """Double buffering is a latency optimization, never a semantic one:
    depth 1 (synchronous) and depth 3 produce identical bits."""
    import dataclasses

    flowsets = [_flows(topo, s) for s in range(4)]
    dims = TopoDims.of(topo)
    f_max = sweep.padded_count(flowsets)
    outs = []
    for depth in (1, 3):
        plan = exec_.plan(dims, cfg, f_max, 512, 4, budget=None,
                          devices=jax.devices()[:min(2, N_DEV)],
                          pipeline_depth=depth)
        # pin the chunking so only the in-flight depth varies
        plan = dataclasses.replace(plan, chunk_width=2)
        assert plan.n_chunks == 2 and plan.pipeline_depth == depth
        outs.append(sweep.run_batch(topo, flowsets, cfg, 512, plan=plan))
    assert np.array_equal(outs[0][1], outs[1][1])
    _states_equal(outs[0][0], outs[1][0], "pipeline depth")


def test_execute_rejects_mismatched_plan(topo, cfg):
    flowsets = [_flows(topo, s) for s in range(3)]
    plan = _plan(cfg, n_lanes=2, budget=None)
    with pytest.raises(ValueError, match="lanes"):
        exec_.execute(plan, [topo] * 3, flowsets, cfg)


@multi_device
def test_sharded_operands_land_on_all_devices(topo, cfg):
    sharding = exec_.lane_sharding(jax.devices())
    x = jax.device_put(np.zeros((N_DEV * 2, 3), np.int32), sharding)
    assert len(x.sharding.device_set) == N_DEV


# ---- run store --------------------------------------------------------------
def test_store_spools_chunks_and_reloads(topo, cfg, tmp_path):
    flowsets = [_flows(topo, s) for s in range(5)]
    per = _plan(cfg, budget=None).per_lane_bytes
    store = exec_.RunStore(tmp_path)
    st, em = sweep.run_batch(topo, flowsets, cfg, 512,
                             max_batch_bytes=2 * per, store=store)
    assert len(store.manifest) == exec_.last_plan().n_chunks
    assert sum(e["lanes"] for e in store.manifest) == 5
    # readback provenance: per-lane active ticks land in the manifest
    assert all(len(e["active_ticks"]) == e["lanes"]
               for e in store.manifest)
    mst, mem = store.load_tag(cfg.proto.name)
    assert np.array_equal(mem, em)
    _states_equal(mst, st, "spooled reload")
    one_st, one_em = store.load_chunk(store.manifest[0]["path"])
    assert np.array_equal(one_em, em[:store.manifest[0]["lanes"]])
    assert isinstance(one_st, engine.SimState)


def test_store_runs_never_interleave_and_manifest_persists(topo, cfg,
                                                           tmp_path):
    """The same tag spooled by two execute() calls (same protocol, two
    groups/scenarios) forms two runs: load_tag returns the latest run —
    never a mix — and the persisted manifest lets a fresh RunStore
    reattach after the process is gone."""
    per = _plan(cfg, budget=None).per_lane_bytes
    store = exec_.RunStore(tmp_path)
    fs_a = [_flows(topo, s) for s in range(3)]
    fs_b = [_flows(topo, s) for s in (7, 8)]
    _, em_a = sweep.run_batch(topo, fs_a, cfg, 512,
                              max_batch_bytes=2 * per, store=store)
    _, em_b = sweep.run_batch(topo, fs_b, cfg, 512,
                              max_batch_bytes=2 * per, store=store)
    assert store.runs_of(cfg.proto.name) == [0, 1]
    _, got_last = store.load_tag(cfg.proto.name)           # latest run
    assert np.array_equal(got_last, em_b)
    _, got_first = store.load_tag(cfg.proto.name, run=0)
    assert np.array_equal(got_first, em_a)

    reattached = exec_.RunStore(tmp_path)                  # fresh process
    assert len(reattached.manifest) == len(store.manifest)
    _, got = reattached.load_tag(cfg.proto.name, run=0)
    assert np.array_equal(got, em_a)


def test_execute_streaming_collect_false(topo, cfg, tmp_path):
    """collect=False spools every chunk but returns None (results live
    only on disk); without a store it must refuse."""
    flowsets = [_flows(topo, s) for s in range(3)]
    dims = TopoDims.of(topo)
    f_max = sweep.padded_count(flowsets)
    per = exec_.plan(dims, cfg, f_max, 512, 3, budget=None).per_lane_bytes
    plan = exec_.plan(dims, cfg, f_max, 512, 3, budget=2 * per)
    st_ref, em_ref = sweep.run_batch(topo, flowsets, cfg, 512)
    store = exec_.RunStore(tmp_path)
    out = exec_.execute(plan, [topo] * 3, flowsets, cfg, store=store,
                        tag="stream", collect=False)
    assert out is None
    mst, mem = store.load_tag("stream")
    assert np.array_equal(mem, em_ref)
    _states_equal(mst, st_ref, "streamed")
    with pytest.raises(ValueError, match="store"):
        exec_.execute(plan, [topo] * 3, flowsets, cfg, collect=False)


def test_store_records_and_writes_bench_json(tmp_path):
    store = exec_.RunStore(tmp_path, run_id="test")
    store.record_scenario("fig5_load_sweep", wall_s=2.0, grid_points=8,
                          xla_compilations=2, device_count=N_DEV,
                          budget_source="host_meminfo",
                          active_ticks_max=512, n_ticks=4000)
    path = store.write_bench(platform="cpu", device_count=N_DEV)
    data = json.loads(path.read_text())
    rec = data["scenarios"]["fig5_load_sweep"]
    assert rec["wall_s"] == 2.0
    assert rec["lanes_per_sec"] == 4.0
    assert rec["xla_compilations"] == 2
    assert rec["device_count"] == N_DEV
    assert rec["active_ticks_max"] == 512 and rec["n_ticks"] == 4000
    assert data["device_count"] == N_DEV and data["run_id"] == "test"
    table = store.summary_table()
    assert "fig5_load_sweep" in table and len(table.splitlines()) == 2
    assert "512/4000" in table


def test_write_bench_merge_appends_trajectory(tmp_path):
    """Re-running the nightly against an existing BENCH_sweep.json must
    extend the per-scenario trajectory, never overwrite it — the
    committed perf record accumulates across PRs."""
    a = exec_.RunStore(tmp_path, run_id="pr5")
    a.record_scenario("fig5_load_sweep", wall_s=4.0, grid_points=8,
                      xla_compilations=2, device_count=1)
    path = a.write_bench(tmp_path / "BENCH_sweep.json")
    b = exec_.RunStore(tmp_path, run_id="pr6")
    b.record_scenario("fig5_load_sweep", wall_s=2.0, grid_points=8,
                      xla_compilations=2, device_count=1)
    b.record_scenario("websearch_tail", wall_s=1.0, grid_points=4,
                      xla_compilations=3, device_count=1)
    data = json.loads(b.write_bench(path).read_text())
    # latest-per-scenario view: run b's record wins for the re-run
    # scenario, and scenarios run a covered are kept
    assert data["run_id"] == "pr6"
    assert data["scenarios"]["fig5_load_sweep"]["wall_s"] == 2.0
    # ... while the trajectory accumulated both runs in order
    traj = data["trajectory"]["fig5_load_sweep"]
    assert [e["run_id"] for e in traj] == ["pr5", "pr6"]
    assert [e["wall_s"] for e in traj] == [4.0, 2.0]
    assert [e["run_id"] for e in data["trajectory"]["websearch_tail"]] == \
        ["pr6"]
    # a partial rerun (one scenario only) keeps the other latest records
    c = exec_.RunStore(tmp_path, run_id="pr7")
    c.record_scenario("websearch_tail", wall_s=0.5, grid_points=4,
                      xla_compilations=3, device_count=1)
    data = json.loads(c.write_bench(path).read_text())
    assert data["scenarios"]["websearch_tail"]["wall_s"] == 0.5
    assert data["scenarios"]["fig5_load_sweep"]["wall_s"] == 2.0
    assert [e["run_id"] for e in data["trajectory"]["websearch_tail"]] == \
        ["pr6", "pr7"]


def test_run_grid_mixed_protocols_through_planner(topo, cfg):
    """Two protocol variants still compile once each under planned
    execution, and every case lands trimmed to its true shapes."""
    fl = [_flows(topo, s) for s in (7, 8)]
    cases = [(f"{p}_s{i}", SimConfig(proto=pr, clos=CLOS), fl[i])
             for p, pr in (("bfc", BFC), ("dctcp", DCTCP))
             for i in range(2)]
    before = engine.trace_count()
    results = sweep.run_grid(topo, cases, n_ticks=512, summarize=False)
    assert engine.trace_count() - before <= 2
    for (label, _, flows), r in zip(cases, results):
        assert r.state.done.shape[0] == flows.n_flows, label
        assert r.emits.shape[1] == 3, label


# ---- fault injection, OOM retry, crash-safe store, resume -------------------
# (the end-to-end OOM+crash+resume scenario also gates CI via
# scripts/fault_guard.py; these tests cover each path in isolation)
import dataclasses
import os as _os
import subprocess
import sys as _sys
from pathlib import Path

from repro.sim.exec import dispatch, faults


@pytest.fixture
def clean_faults():
    faults.clear()
    yield
    faults.clear()


def _chunked_plan(cfg, n_lanes, chunk_width, n_ticks=512):
    """A single-device plan with a pinned chunk width (the fault sites
    are chunk indices, so tests need a known chunking)."""
    base = _plan(cfg, n_lanes=n_lanes, n_ticks=n_ticks, budget=None,
                 devices=jax.devices()[:1])
    return dataclasses.replace(base, chunk_width=chunk_width)


def test_fault_spec_parse_valid_and_invalid():
    specs = faults.parse(" oom@chunk2:1, crash@spool3 ,kill@spool0:2 ")
    assert [(s.kind, s.site, s.index, s.count) for s in specs] == \
        [("oom", "chunk", 2, 1), ("crash", "spool", 3, 1),
         ("kill", "spool", 0, 2)]
    assert faults.parse("") == []
    for bad in ("oom@chunk", "oom#chunk2", "frob@chunk2", "oom@disk2",
                "oom@chunk2:x"):
        with pytest.raises(ValueError):
            faults.parse(bad)


def test_injector_counts_decrement(clean_faults):
    inj = faults.install("oom@chunk1:2")
    with pytest.raises(faults.SimulatedOOM):
        inj.fire("chunk", 1)
    inj.fire("chunk", 0)                       # wrong index: no-op
    inj.fire("spool", 1)                       # wrong site: no-op
    with pytest.raises(faults.SimulatedOOM):
        inj.fire("chunk", 1)
    inj.fire("chunk", 1)                       # count spent: disarmed
    assert not inj.armed()
    assert inj.fired == ["oom@chunk1", "oom@chunk1"]


def test_is_oom_classifies_injected_and_real_messages():
    assert faults.is_oom(faults.SimulatedOOM("chunk", 0))
    assert faults.is_oom(RuntimeError("RESOURCE_EXHAUSTED: ..."))
    assert faults.is_oom(RuntimeError("Allocator ran out of memory"))
    assert not faults.is_oom(RuntimeError("shape mismatch"))


def test_oom_retry_bisects_and_matches_clean_run(topo, cfg, clean_faults):
    flowsets = [_flows(topo, s) for s in range(4)]
    plan = _chunked_plan(cfg, 4, 2)
    st_ref, em_ref = exec_.execute(plan, [topo] * 4, flowsets, cfg,
                                   tag="clean")
    mark = dispatch.RETRY_LOG.mark()
    faults.install("oom@chunk1:1")
    st, em = exec_.execute(plan, [topo] * 4, flowsets, cfg, tag="retried")
    assert np.array_equal(em, em_ref)
    _states_equal(st, st_ref, "OOM-retried run")
    events = dispatch.RETRY_LOG.since(mark)
    assert events and events[0]["chunk"] == 1 \
        and events[0]["retry_width"] == 1
    assert exec_.last_timing()["retries"] == 1


def test_retry_budget_exhaustion_raises_exec_error(topo, cfg,
                                                   clean_faults):
    flowsets = [_flows(topo, s) for s in range(4)]
    plan = _chunked_plan(cfg, 4, 2)
    faults.install("oom@chunk0:99")            # never stops OOMing
    with pytest.raises(exec_.ExecError) as ei:
        exec_.execute(plan, [topo] * 4, flowsets, cfg, tag="doomed")
    assert ei.value.chunk == 0 and ei.value.lanes == (0, 2)
    assert "lanes=[0, 2)" in str(ei.value)
    assert isinstance(ei.value.cause, faults.SimulatedOOM)


def test_crash_mid_spool_then_resume_bit_identical(topo, cfg, tmp_path,
                                                   clean_faults):
    """A crash after chunk 1's tmp write but before its atomic rename
    loses only the in-flight chunk; resume reuses the journaled chunk 0
    and recomputes the rest, matching an uninterrupted run exactly."""
    flowsets = [_flows(topo, s) for s in range(6)]
    plan = _chunked_plan(cfg, 6, 2)
    st_ref, em_ref = exec_.execute(plan, [topo] * 6, flowsets, cfg,
                                   tag="ref")
    store = exec_.RunStore(tmp_path)
    faults.install("crash@spool1")
    with pytest.raises(faults.SimulatedCrash):
        exec_.execute(plan, [topo] * 6, flowsets, cfg, store=store,
                      tag="bfc")
    faults.clear()
    assert [e["chunk"] for e in store.manifest if e["tag"] == "bfc"] == [0]
    assert any(".tmp" in p.name for p in store.chunk_dir.iterdir())

    store2 = exec_.RunStore(tmp_path)          # reattach, fresh process
    st, em = exec_.resume(plan, [topo] * 6, flowsets, cfg, store2,
                          tag="bfc")
    assert np.array_equal(em, em_ref)
    _states_equal(st, st_ref, "resumed run")
    t = exec_.last_timing()
    assert t["chunks_reused"] == 1 and t["retries"] == 0
    _, em_disk = store2.load_tag("bfc")
    assert np.array_equal(em_disk, em_ref)


def test_resume_is_noop_when_run_complete(topo, cfg, tmp_path):
    flowsets = [_flows(topo, s) for s in range(4)]
    plan = _chunked_plan(cfg, 4, 2)
    store = exec_.RunStore(tmp_path)
    st_ref, em_ref = exec_.execute(plan, [topo] * 4, flowsets, cfg,
                                   store=store, tag="bfc")
    before = engine.trace_count()
    st, em = exec_.resume(plan, [topo] * 4, flowsets, cfg, store,
                          tag="bfc")
    assert engine.trace_count() == before      # pure reload, no dispatch
    assert exec_.last_timing()["chunks_reused"] == plan.n_chunks
    assert np.array_equal(em, em_ref)
    _states_equal(st, st_ref, "no-op resume")


def test_resume_without_prior_run_degrades_to_execute(topo, cfg,
                                                      tmp_path):
    flowsets = [_flows(topo, s) for s in range(2)]
    plan = _chunked_plan(cfg, 2, 2)
    store = exec_.RunStore(tmp_path)
    st, em = exec_.resume(plan, [topo] * 2, flowsets, cfg, store,
                          tag="fresh")
    assert exec_.last_timing()["chunks_reused"] == 0
    assert store.runs_of("fresh") == [0]
    with pytest.raises(ValueError, match="store"):
        exec_.execute(plan, [topo] * 2, flowsets, cfg, resume=True)


def test_store_quarantines_truncated_chunk(topo, cfg, tmp_path):
    """A truncated npz (hash mismatch) is quarantined and skipped with a
    warning; load_tag reassembles the surviving lanes instead of raising
    mid-np.load."""
    flowsets = [_flows(topo, s) for s in range(4)]
    plan = _chunked_plan(cfg, 4, 2)
    store = exec_.RunStore(tmp_path)
    exec_.execute(plan, [topo] * 4, flowsets, cfg, store=store, tag="bfc")
    victim = store.manifest[0]
    data = open(victim["path"], "rb").read()
    with open(victim["path"], "wb") as f:      # truncate to half
        f.write(data[:len(data) // 2])
    with pytest.warns(UserWarning, match="quarantined chunk 0"):
        _, em = store.load_tag("bfc")
    assert em.shape[0] == 2                    # only chunk 1's lanes
    assert victim["quarantined"]
    assert (store.quarantine_dir / Path(victim["path"]).name).exists()
    # the quarantine persisted: a reattached store skips it silently
    # (already marked) and a resume would recompute it
    again = exec_.RunStore(tmp_path)
    assert again.manifest[0]["quarantined"]


def test_store_quarantines_missing_chunk_and_reports_empty_run(
        topo, cfg, tmp_path):
    flowsets = [_flows(topo, s) for s in range(4)]
    plan = _chunked_plan(cfg, 4, 2)
    store = exec_.RunStore(tmp_path)
    exec_.execute(plan, [topo] * 4, flowsets, cfg, store=store, tag="bfc")
    Path(store.manifest[0]["path"]).unlink()
    with pytest.warns(UserWarning, match="missing"):
        _, em = store.load_tag("bfc")
    assert em.shape[0] == 2
    Path(store.manifest[1]["path"]).unlink()   # now nothing survives
    with pytest.warns(UserWarning):
        with pytest.raises(exec_.ExecError, match="missing or quarant"):
            store.load_tag("bfc")


def test_store_duplicate_journal_entries_keep_latest(topo, cfg, tmp_path):
    flowsets = [_flows(topo, s) for s in range(2)]
    plan = _chunked_plan(cfg, 2, 2)
    store = exec_.RunStore(tmp_path)
    _, em_ref = exec_.execute(plan, [topo] * 2, flowsets, cfg,
                              store=store, tag="bfc")
    store.manifest.append(dict(store.manifest[0]))   # duplicate record
    store._persist_manifest()
    reattached = exec_.RunStore(tmp_path)
    with pytest.warns(UserWarning, match="duplicate"):
        _, em = reattached.load_tag("bfc")
    assert np.array_equal(em, em_ref)


def test_write_bench_atomic_under_failed_replace(tmp_path, monkeypatch):
    """A crash (or failure) at the commit point must leave the existing
    BENCH file untouched — never truncated."""
    store = exec_.RunStore(tmp_path, run_id="a")
    store.record_scenario("s", wall_s=1.0, grid_points=4,
                          xla_compilations=1, device_count=1)
    path = store.write_bench(tmp_path / "BENCH_sweep.json")
    before = path.read_text()

    from repro.sim.exec import store as store_mod

    def boom(src, dst):
        raise OSError("disk pulled at the worst moment")
    monkeypatch.setattr(store_mod.os, "replace", boom)
    b = exec_.RunStore(tmp_path, run_id="b")
    b.record_scenario("s", wall_s=0.5, grid_points=4,
                      xla_compilations=1, device_count=1)
    with pytest.raises(OSError):
        b.write_bench(path)
    monkeypatch.undo()
    assert path.read_text() == before          # old content, still valid
    assert json.loads(before)["run_id"] == "a"


def test_plan_carries_retry_policy(cfg):
    p = _plan(cfg, budget=None)
    assert p.retry == exec_.RetryPolicy()
    pol = exec_.RetryPolicy(max_retries=2, min_width=1, backoff_s=0.5)
    assert _plan(cfg, budget=None, retry=pol).retry is pol
    assert pol.backoff_for(0) == 0.5 and pol.backoff_for(2) == 2.0


@pytest.mark.slow
def test_kill_mid_spool_subprocess_then_resume(topo, cfg, tmp_path):
    """The hard-death variant: a child process dies via os._exit(137) —
    no unwinding, no atexit — while spooling chunk 1; the parent
    reattaches the store and resumes to a bit-identical result."""
    flowsets = [_flows(topo, s) for s in range(4)]
    plan = _chunked_plan(cfg, 4, 2)
    st_ref, em_ref = exec_.execute(plan, [topo] * 4, flowsets, cfg,
                                   tag="ref")
    child = f"""
import dataclasses, jax
from repro.sim import topology, workload
from repro.sim import exec as exec_
from repro.sim.config import BFC, SimConfig
from repro.sim.topology import ClosParams, TopoDims
CLOS = ClosParams(n_servers=8, n_tor=2, n_spine=2, switch_buffer_pkts=512)
topo = topology.build(CLOS)
cfg = SimConfig(proto=BFC, clos=CLOS)
fs = [workload.generate(topo, workload.WorkloadParams(
    workload="uniform", load=0.5, seed=s), 24) for s in range(4)]
base = exec_.plan(TopoDims.of(topo), cfg, 64, 512, 4, budget=None,
                  devices=jax.devices()[:1])
plan = dataclasses.replace(base, chunk_width=2)
store = exec_.RunStore({str(tmp_path)!r})
exec_.execute(plan, [topo] * 4, fs, cfg, store=store, tag="bfc")
raise SystemExit("unreachable: the kill fault should have fired")
"""
    env = dict(_os.environ, REPRO_FAULTS="kill@spool1",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=_os.pathsep.join(
                   [_os.path.join(_os.path.dirname(__file__), "..", "src")]
                   + ([_os.environ["PYTHONPATH"]]
                      if _os.environ.get("PYTHONPATH") else [])))
    env.pop("XLA_FLAGS", None)                 # child: plain single device
    proc = subprocess.run([_sys.executable, "-c", child],
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 137, proc.stderr

    store = exec_.RunStore(tmp_path)           # parent reattaches
    assert [e["chunk"] for e in store.manifest if e["tag"] == "bfc"] == [0]
    st, em = exec_.resume(plan, [topo] * 4, flowsets, cfg, store,
                          tag="bfc")
    assert np.array_equal(em, em_ref)
    _states_equal(st, st_ref, "resumed after kill")
    assert exec_.last_timing()["chunks_reused"] == 1
