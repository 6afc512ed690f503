"""The fabric's port graph: the k-ary fat-tree's invariants, and both
fabrics against the benchmark's own tables, which are written
independently of `repro.sim.topology` (`bench/flowgen.py` for the
leaf-spine, `bench/fat_tree.py` for the fat-tree).

These pin the tables and routes every lane runs on; the last test runs a
registry scenario on a fat-tree through `scenarios.run`."""
import sys
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.tier1

from repro.sim import scenarios, topology, workload
from repro.sim.topology import (ClosParams, FatTreeParams, TopoDims,
                                pack_topo)

BENCH = Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import fat_tree  # noqa: E402  (bench module: imports nothing of the program)
import flowgen  # noqa: E402

# k: (egress ports, switches, cores)
SHAPES = {4: (96, 20, 4), 8: (768, 80, 16)}


def _random_flows(n_servers, n=4000, seed=0):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_servers, n)
    dst = (src + rng.integers(1, n_servers, n)) % n_servers
    fid = rng.integers(0, 2 ** 31, n).astype(np.int32)
    return src, dst, fid


@pytest.mark.parametrize("k", sorted(SHAPES))
def test_fat_tree_counts(k):
    topo = topology.build(FatTreeParams(k=k))
    ports, switches, _ = SHAPES[k]
    assert (topo.n_ports, topo.n_switches, topo.hops) == (ports, switches, 6)
    assert topo.n_servers == k ** 3 // 4
    assert TopoDims.of(topo).hops == 6
    assert int(topo.port_is_nic.sum()) == topo.n_servers
    # every switch has k ports
    assert np.array_equal(np.bincount(topo.port_switch[~topo.port_is_nic]),
                          np.full(switches, k))


@pytest.mark.parametrize("k", sorted(SHAPES))
def test_fat_tree_routes_follow_the_graph(k):
    """Each hop leaves from a port of the switch the previous hop feeds, a
    route starts at its source's NIC and ends on the destination's edge
    down-port, which feeds no switch; a route's length says how far apart
    the hosts are (2, 4 or 6 transmissions)."""
    topo = topology.build(FatTreeParams(k=k))
    src, dst, fid = _random_flows(topo.n_servers)
    routes = topo.routes(src, dst, fid)
    assert routes.shape == (len(src), 6) and routes.dtype == np.int32
    n_hops = (routes >= 0).sum(1)
    # valid hops are a prefix
    assert np.array_equal(routes >= 0, np.arange(6) < n_hops[:, None])
    assert np.array_equal(routes[:, 0], src)
    h = k // 2
    same_edge = src // h == dst // h
    same_pod = src // (h * h) == dst // (h * h)
    assert np.array_equal(n_hops, np.where(same_edge, 2,
                                           np.where(same_pod, 4, 6)))
    for hop in range(1, 6):
        on = routes[:, hop] >= 0
        prev = routes[on, hop - 1]
        assert np.array_equal(topo.port_switch[routes[on, hop]],
                              topo.feeds[prev])
    last = routes[np.arange(len(src)), n_hops - 1]
    assert (topo.feeds[last] == -1).all()
    assert not topo.port_is_nic[last].any()
    # the last hop delivers to the destination: its edge's down-port
    assert np.array_equal(topo.port_switch[last], dst // h)


@pytest.mark.parametrize("k", sorted(SHAPES))
def test_fat_tree_ecmp_reaches_every_core(k):
    topo = topology.build(FatTreeParams(k=k))
    src, dst, fid = _random_flows(topo.n_servers)
    routes = topo.routes(src, dst, fid)
    core0 = 2 * k * (k // 2)
    far = routes[:, 3][(routes >= 0).sum(1) == 6]
    cores = set(topo.port_switch[far].tolist())
    assert cores == set(range(core0, core0 + SHAPES[k][2]))


def test_fat_tree_feeds_follow_the_links():
    """A port feeds the switch at the far end of its link, and that switch
    has a port back: the graph is the folded Clos's undirected links."""
    topo = topology.build(FatTreeParams(k=4))
    sw_ports = np.nonzero(~topo.port_is_nic)[0]
    links = {(int(topo.port_switch[p]), int(topo.feeds[p]))
             for p in sw_ports if topo.feeds[p] >= 0}
    assert all((b, a) in links for a, b in links)
    # 16 edge-aggregation and 16 aggregation-core links at k = 4, each
    # seen from both ends
    assert len(links) == 2 * (16 + 16)


@pytest.mark.parametrize("k", sorted(SHAPES))
def test_fat_tree_matches_bench_module(k):
    topo = topology.build(FatTreeParams(k=k))
    bench = fat_tree.FatTree(k=k, prop_ticks=12, switch_buffer_pkts=12288)
    assert (bench.n_ports, bench.n_servers, bench.n_switches) == \
        (topo.n_ports, topo.n_servers, topo.n_switches)
    assert np.array_equal(bench.port_switch(), topo.port_switch)
    assert np.array_equal(bench.feeds(), topo.feeds)
    src, dst, fid = _random_flows(topo.n_servers, n=1000, seed=k)
    assert np.array_equal(bench.routes(src, dst, fid),
                          topo.routes(src, dst, fid))


def test_leaf_spine_matches_bench_fabric():
    """`build(ClosParams())` keeps the paper's leaf-spine: its tables and
    routes equal the benchmark's own `flowgen.Fabric`."""
    topo = topology.build(ClosParams())
    bench = flowgen.Fabric(n_servers=128, n_tor=8, n_spine=8, prop_ticks=12,
                           switch_buffer_pkts=12288)
    assert (topo.n_ports, topo.n_switches, topo.hops) == (384, 16, 4)
    assert bench.n_ports == topo.n_ports
    assert np.array_equal(bench.port_switch(), topo.port_switch)
    assert np.array_equal(bench.feeds(), topo.feeds)
    assert np.array_equal(np.asarray(pack_topo(topo).feeds), bench.feeds())
    src, dst, fid = _random_flows(128, seed=5)
    assert np.array_equal(bench.routes(src, dst, fid),
                          topo.routes(src, dst, fid))


@pytest.mark.parametrize("params, core_links", [
    (ClosParams(), 64), (FatTreeParams(k=8), 128), (FatTreeParams(k=4), 16)])
def test_load_is_normalised_over_the_core(params, core_links):
    """The generator offers `load` of the fabric's core links: the
    leaf-spine's ToR-to-spine links or the fat-tree's aggregation-to-core
    links, carrying the flows that cross them (1 - 1/8 at both k = 8 and
    the paper's 8 ToRs)."""
    assert params.core_links == core_links
    topo = topology.build(params)
    wp = workload.WorkloadParams(workload="uniform", load=0.5, seed=2,
                                 sigma=0.5)
    flows = workload.generate(topo, wp, 4000)
    assert flows.routes.shape[1] == topo.hops
    mean_size = flows.size_pkts.mean()
    rate = flows.n_flows / flows.horizon
    offered = rate * mean_size * params.core_share / core_links
    assert 0.45 < offered < 0.55


def test_fat_tree_needs_an_even_k():
    with pytest.raises(ValueError):
        FatTreeParams(k=5)


def test_scenario_runs_on_a_fat_tree():
    res = scenarios.run("fig5_load_sweep", clos=FatTreeParams(k=4),
                        n_flows=32, n_ticks=300)
    sc = scenarios.get("fig5_load_sweep")
    assert len(res) == sc.grid_size()
    for r in res:
        assert r.cfg.clos == FatTreeParams(k=4)
        assert r.flows.routes.shape[1] == 6
        assert r.state.f_q.shape[1] == 6
        assert r.metrics.completed > 0
    assert scenarios.topo_tag(FatTreeParams(k=4)) == "k4b12288p12"
