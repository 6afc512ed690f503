"""Fabrics of the packet simulator: one port graph, any topology.

Everything that transmits is an *egress port*. Ports are flattened into one
global index space so the whole network updates as dense arrays: the
server NIC uplinks first, `[0, n_servers)`, then the switches' ports,
switch by switch. A fabric is three per-port tables and a route builder
(`Topology`):

* `port_switch[p]`: the switch owning port p (-1 for a NIC);
* `port_is_nic[p]`;
* `feeds[p]`: the switch whose buffer a packet sent from p enters (PFC and
  buffer accounting), -1 where p delivers to a server;
* `routes(src, dst, fid)`: the sequence of egress ports each flow is
  *transmitted from*, `hops` columns wide, -1 padded. The hop after the
  last valid port is delivery at the destination server.

`build(params)` makes one from either fabric's parameters:

`ClosParams`, the paper's two-tier leaf-spine (section 6: 128 servers, 8
ToRs of 16 servers, 8 spines, 2:1 oversubscription), `hops = 4`:

  [0, n_servers)                         server NIC uplink ports
  [nic_end, nic_end + n_tor*ports_tor)   ToR ports: per ToR, first
                                         `servers_per_tor` down-ports (to its
                                         servers) then `n_spine` up-ports
  [tor_end, tor_end + n_spine*n_tor)     spine down-ports (to each ToR)

  inter-ToR: [src NIC, src ToR up-port(spine s), spine s down-port(dst ToR),
              dst ToR down-port(dst server)]
  intra-ToR: [src NIC, dst ToR down-port(dst server), -1, -1]

`FatTreeParams`, Al-Fares et al.'s k-ary three-tier fat-tree (SIGCOMM 2008,
section 3: k pods of k/2 edge and k/2 aggregation switches, (k/2)^2 cores,
k^3/4 hosts, k ports a switch, full bisection), `hops = 6`. With h = k/2:
switches 0..k*h-1 are edges, the next k*h aggregations, then h*h cores;
pod p holds edges h*p..h*p+h-1 and the aggregations at the same offsets.
Every switch has k ports, numbered after the NICs switch by switch: an
edge's h down-ports (its hosts, in host order) then h up-ports (its pod's
aggregations, in order); an aggregation's h down-ports (its pod's edges)
then h up-ports, up-port i of aggregation j going to core h*j + i; a
core's k down-ports, one per pod.

  same edge:  [src NIC, edge down(dst)]
  same pod:   [src NIC, edge up j, agg j down(dst edge), edge down(dst)]
  other pod:  [src NIC, edge up j, agg j up i, core h*j+i down(dst pod),
               agg j down(dst edge), edge down(dst)]

Flow-level ECMP picks j and i by two independent hashes of the flow id
(the leaf-spine's spine by the first).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, NamedTuple, Union

import jax.numpy as jnp
import numpy as np

from ..core.hashing import ecmp_choice


@dataclass(frozen=True)
class ClosParams:
    n_servers: int = 128
    n_tor: int = 8
    n_spine: int = 8
    # timing, in ticks (1 tick = one MTU transmission time at line rate:
    # 1 KB at 100 Gbps = 80 ns)
    prop_ticks: int = 12          # ~1 us per link
    switch_buffer_pkts: int = 12288  # 12 MB of 1 KB packets

    @property
    def servers_per_tor(self) -> int:
        assert self.n_servers % self.n_tor == 0
        return self.n_servers // self.n_tor

    @property
    def ports_per_tor(self) -> int:
        return self.servers_per_tor + self.n_spine

    # -- what the workload generator normalises load over ---------------------
    @property
    def servers_per_rack(self) -> int:
        return self.servers_per_tor

    @property
    def core_links(self) -> int:
        """ToR-to-spine links, each one packet a tick."""
        return self.n_tor * self.n_spine

    @property
    def core_share(self) -> float:
        """Share of uniformly drawn flows whose path crosses the core."""
        return 1.0 - 1.0 / self.n_tor


@dataclass(frozen=True)
class FatTreeParams:
    """A k-ary fat-tree (Al-Fares, Loukissas and Vahdat, SIGCOMM 2008)."""
    k: int = 8
    prop_ticks: int = 12
    switch_buffer_pkts: int = 12288

    def __post_init__(self):
        if self.k < 2 or self.k % 2:
            raise ValueError(f"a fat-tree needs an even k >= 2, got {self.k}")

    @property
    def n_servers(self) -> int:
        return self.k ** 3 // 4

    @property
    def servers_per_rack(self) -> int:
        return self.k // 2

    @property
    def core_links(self) -> int:
        """Aggregation-to-core links, each one packet a tick."""
        return self.k ** 3 // 4

    @property
    def core_share(self) -> float:
        """Share of uniformly drawn flows whose path crosses the core
        (another pod)."""
        return 1.0 - 1.0 / self.k


FabricParams = Union[ClosParams, FatTreeParams]


@dataclass(eq=False)
class Topology:
    """A fabric's port graph: per-port tables and its route builder.
    Treated as immutable after `build`."""
    params: FabricParams
    n_ports: int
    n_switches: int
    hops: int                    # route width: transmissions on the longest path
    port_switch: np.ndarray      # (n_ports,) owning switch; -1 for NIC ports
    port_is_nic: np.ndarray      # (n_ports,) bool
    feeds: np.ndarray            # (n_ports,) switch fed by the port; -1 = server
    route: Callable = field(repr=False)

    @property
    def n_servers(self) -> int:
        return self.params.n_servers

    def routes(self, src, dst, fid) -> np.ndarray:
        """(n_flows, hops) int32 egress port ids of each flow's path, -1
        padded; ECMP choices hash the flow id `fid`."""
        return self.route(np.asarray(src), np.asarray(dst),
                          np.asarray(fid, np.int32))


def _ecmp(fid: np.ndarray, n_paths: int, level: int = 0) -> np.ndarray:
    return np.asarray(ecmp_choice(jnp.asarray(fid), n_paths, level))


def _leaf_spine(p: ClosParams) -> Topology:
    per, n_tor, n_spine = p.servers_per_tor, p.n_tor, p.n_spine
    ppt = p.ports_per_tor
    tor_base = p.n_servers
    spine_base = tor_base + n_tor * ppt
    n_ports = spine_base + n_spine * n_tor

    def tor_down(tor, server):
        return tor_base + tor * ppt + server % per

    def tor_up(tor, spine):
        return tor_base + tor * ppt + per + spine

    def spine_down(spine, tor):
        return spine_base + spine * n_tor + tor

    port_switch = np.full(n_ports, -1, np.int32)
    port_switch[tor_base:spine_base] = np.repeat(np.arange(n_tor), ppt)
    port_switch[spine_base:] = n_tor + np.repeat(np.arange(n_spine), n_tor)
    feeds = np.full(n_ports, -1, np.int32)
    feeds[:p.n_servers] = np.arange(p.n_servers) // per   # NIC -> its ToR
    tor, sp = np.meshgrid(np.arange(n_tor), np.arange(n_spine),
                          indexing="ij")
    feeds[tor_up(tor, sp)] = n_tor + sp                   # ToR up -> spine
    feeds[spine_down(sp, tor)] = tor                      # spine down -> ToR
    # ToR down-ports feed servers: stay -1

    def route(src, dst, fid):
        routes = np.full((src.shape[0], 4), -1, np.int32)
        s_tor, d_tor = src // per, dst // per
        routes[:, 0] = src
        intra = s_tor == d_tor
        routes[intra, 1] = tor_down(d_tor[intra], dst[intra])
        inter = ~intra
        spine = _ecmp(fid, n_spine)[inter]
        routes[inter, 1] = tor_up(s_tor[inter], spine)
        routes[inter, 2] = spine_down(spine, d_tor[inter])
        routes[inter, 3] = tor_down(d_tor[inter], dst[inter])
        return routes

    return Topology(params=p, n_ports=n_ports, n_switches=n_tor + n_spine,
                    hops=4, port_switch=port_switch,
                    port_is_nic=port_switch < 0, feeds=feeds, route=route)


def _fat_tree(p: FatTreeParams) -> Topology:
    k, h = p.k, p.k // 2
    n_host, n_edge, n_core = p.n_servers, k * h, h * h
    agg0, core0 = n_edge, 2 * n_edge
    n_sw = 2 * n_edge + n_core
    n_ports = n_host + n_sw * k

    def port(switch, local):
        return n_host + switch * k + local

    port_switch = np.full(n_ports, -1, np.int32)
    port_switch[n_host:] = np.repeat(np.arange(n_sw), k)
    feeds = np.full(n_ports, -1, np.int32)
    feeds[:n_host] = np.arange(n_host) // h               # NIC -> its edge
    e = np.arange(n_edge)        # edge e, or aggregation agg0 + e, of pod e // h
    c = np.arange(n_core)
    for x in range(h):
        feeds[port(e, h + x)] = agg0 + (e // h) * h + x           # edge up
        feeds[port(agg0 + e, x)] = (e // h) * h + x               # agg down
        feeds[port(agg0 + e, h + x)] = core0 + (e % h) * h + x    # agg up
    for pod in range(k):
        feeds[port(core0 + c, pod)] = agg0 + pod * h + c // h     # core down
    # edge down-ports feed servers: stay -1

    def route(src, dst, fid):
        routes = np.full((src.shape[0], 6), -1, np.int32)
        j, i = _ecmp(fid, h, 0), _ecmp(fid, h, 1)
        s_edge, d_edge = src // h, dst // h
        s_pod, d_pod = s_edge // h, d_edge // h
        last = port(d_edge, dst % h)
        agg_down = port(agg0 + d_pod * h + j, d_edge % h)
        routes[:, 0] = src
        edge = s_edge == d_edge
        pod = (s_pod == d_pod) & ~edge
        far = s_pod != d_pod
        routes[edge, 1] = last[edge]
        routes[~edge, 1] = port(s_edge, h + j)[~edge]
        routes[pod, 2] = agg_down[pod]
        routes[pod, 3] = last[pod]
        routes[far, 2] = port(agg0 + s_pod * h + j, h + i)[far]
        routes[far, 3] = port(core0 + h * j + i, d_pod)[far]
        routes[far, 4] = agg_down[far]
        routes[far, 5] = last[far]
        return routes

    return Topology(params=p, n_ports=n_ports, n_switches=n_sw, hops=6,
                    port_switch=port_switch, port_is_nic=port_switch < 0,
                    feeds=feeds, route=route)


def build(params: FabricParams) -> Topology:
    if isinstance(params, FatTreeParams):
        return _fat_tree(params)
    return _leaf_spine(params)


# Cached variant for callers that rebuild the same fabric repeatedly (the
# sweep subsystem derives a per-case Topology from each SimConfig.clos).
# Topology is treated as immutable after build(); callers must not mutate.
build_cached = functools.lru_cache(maxsize=None)(build)


class TopoDims(NamedTuple):
    """The topology-derived *shapes* of the compiled simulator program.

    Everything else about a fabric (port->switch map, PFC feed graph, buffer
    limit, link propagation delay) is a traced `TopoOperands`; only these
    dims — plus the protocol / timing config — key the XLA compile cache.
    Two fabrics with equal dims share one executable, and `sweep.py` pads a
    mixed-topology batch up to a common `TopoDims` so topology can ride the
    vmap batch axis.

    `prop_max` is the padded wire-ring length: each lane's wires are
    `(P, prop_max)` arrays, but indexing wraps at the lane's own traced
    `TopoOperands.prop_ticks` modulus, so fabrics with different link
    delays still share one program (slots beyond a lane's true delay are
    never touched). `hops` is the route width H: the per-(flow, hop)
    tables are (F, H) and the feedback rings hold H * prop_max + 2 rows;
    a lane with shorter routes pads them with -1 (hops it never takes)."""
    n_ports: int
    n_servers: int
    n_switches: int
    prop_max: int
    hops: int

    @classmethod
    def of(cls, topo: Topology) -> "TopoDims":
        return cls(n_ports=topo.n_ports, n_servers=topo.n_servers,
                   n_switches=topo.n_switches,
                   prop_max=topo.params.prop_ticks, hops=topo.hops)

    def union(self, other: "TopoDims") -> "TopoDims":
        return TopoDims(*(max(a, b) for a, b in zip(self, other)))


class TopoOperands(NamedTuple):
    """Per-fabric tables fed to the jitted step as traced operands.

    Shapes are fixed by `TopoDims` per compiled program: (P,) / (NSW,) / ().
    `sweep.py` stacks these along a leading batch axis (next to
    `engine.FlowOperands`) so one compilation serves a whole
    topology x workload x seed grid. Per-flow routing tables ride in
    `FlowOperands.routes` — flows are generated against their lane's fabric —
    so `TopoOperands` only carries flow-independent port/switch tables.

    Padding contract (mirrors the phantom-flow contract in `sweep.py`):
    ports / servers / switches appended beyond a fabric's real counts are
    inert phantoms. A phantom port never holds occupancy (no route names it),
    never transmits (occupancy gates eligibility), and is masked out of
    port-keyed statistics by `port_valid`; a phantom switch accumulates no
    occupancy and is masked out of `occ_hist` by `switch_valid`; a phantom
    server never sources flows, so its NIC lane never wins the DRR
    segment-min. Wire-ring slots beyond a lane's `prop_ticks` (up to the
    padded `TopoDims.prop_max`) are phantom too: indexing wraps at the
    traced modulus, so they are never written or read. A padded run is
    bit-identical to the unpadded run (tests/test_sim_topo_sweep.py)."""
    port_switch: jnp.ndarray   # (P,) owning switch; -1 for NIC + phantom
    port_is_nic: jnp.ndarray   # (P,) bool
    port_valid: jnp.ndarray    # (P,) bool, False for phantom padding
    feeds: jnp.ndarray         # (P,) switch fed by the port; -1 = a server
    switch_valid: jnp.ndarray  # (NSW,) bool, False for phantom padding
    buffer_limit: jnp.ndarray  # () i32 drop threshold (huge if infinite)
    occ_ref: jnp.ndarray       # () i32 occupancy-histogram reference scale
    prop_ticks: jnp.ndarray    # () i32 link delay = wire-ring wrap modulus


def pack_topo(topo: Topology, *, infinite_buffer: bool = False,
              dims: "TopoDims | None" = None) -> TopoOperands:
    """Derive the traced operand bundle for `topo`, padded to `dims`;
    phantom ports feed no switch."""
    p0 = topo.params
    dims = dims or TopoDims.of(topo)
    P, NSW = dims.n_ports, dims.n_switches
    if any(d < t for d, t in zip(dims, TopoDims.of(topo))):
        raise ValueError(f"dims {dims} smaller than topology")

    port_switch = np.full(P, -1, np.int32)
    port_switch[:topo.n_ports] = topo.port_switch
    port_is_nic = np.zeros(P, bool)
    port_is_nic[:topo.n_ports] = topo.port_is_nic
    port_valid = np.zeros(P, bool)
    port_valid[:topo.n_ports] = True
    switch_valid = np.zeros(NSW, bool)
    switch_valid[:topo.n_switches] = True
    feeds = np.full(P, -1, np.int32)
    feeds[:topo.n_ports] = topo.feeds

    buffer_limit = (1 << 29) if infinite_buffer else p0.switch_buffer_pkts
    return TopoOperands(
        port_switch=jnp.asarray(port_switch),
        port_is_nic=jnp.asarray(port_is_nic),
        port_valid=jnp.asarray(port_valid),
        feeds=jnp.asarray(feeds),
        switch_valid=jnp.asarray(switch_valid),
        buffer_limit=jnp.int32(buffer_limit),
        occ_ref=jnp.int32(p0.switch_buffer_pkts),
        prop_ticks=jnp.int32(p0.prop_ticks))


def path_prop_ticks(routes: np.ndarray, prop_ticks: int) -> np.ndarray:
    """One-way propagation delay (ticks) of each flow's path."""
    hops = (routes >= 0).sum(axis=1)  # number of transmissions
    return hops * prop_ticks


def ideal_fct_ticks(routes: np.ndarray, size_pkts: np.ndarray,
                    prop_ticks: int) -> np.ndarray:
    """Best-possible FCT: store-and-forward pipeline at line rate on an idle
    network: size serialization + per-hop propagation."""
    return size_pkts + path_prop_ticks(routes, prop_ticks)
