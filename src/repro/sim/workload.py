"""Workload synthesis (paper §4.1, Fig. 2).

Flow sizes are drawn from piecewise log-linear CDFs matching the three
industry workloads in Fig. 2 (the same sources as Homa [37]):

  * ``google``     -- "All applications in a Google data center": mix from
                      single-packet RPCs up to ~100 MB; ~50% of *bytes* in
                      flows < ~100 KB.
  * ``fb_hadoop``  -- Facebook Hadoop: mostly sub-BDP flows by count, bytes
                      concentrated in the 100 KB - 10 MB range.
  * ``websearch``  -- DCTCP WebSearch: heavy-tailed, bytes dominated by
                      multi-MB flows.

Arrivals: lognormal inter-arrival times (sigma = 2, paper §4.1) scaled so the
offered load on the fabric's core links (the leaf-spine's ToR-to-spine links,
a fat-tree's aggregation-to-core links) equals the target. Source/destination
pairs uniform (or rack-local with probability `locality`, App. B). Incast:
synchronized N-to-1 transfers of `incast_total_kb` aggregate, injected as a
Poisson process sized to consume `incast_load` of capacity (§4).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from .topology import Topology, ideal_fct_ticks

# (size_in_KB, CDF-by-*count*) control points. Derived from the published
# byte-weighted CDFs; interpolation is log-linear in size.
_SIZE_CDFS = {
    # Google all-apps: many tiny RPCs, tail to 100 MB
    "google": [(1, 0.35), (2, 0.45), (4, 0.55), (8, 0.62), (16, 0.70),
               (32, 0.77), (64, 0.83), (128, 0.88), (256, 0.92), (512, 0.95),
               (1024, 0.97), (4096, 0.988), (16384, 0.996), (65536, 1.0)],
    # FB Hadoop: dominated by small flows by count; bytes in 0.1-10 MB
    "fb_hadoop": [(1, 0.50), (2, 0.62), (4, 0.70), (8, 0.75), (16, 0.79),
                  (32, 0.83), (64, 0.87), (128, 0.91), (256, 0.94),
                  (512, 0.96), (1024, 0.975), (2048, 0.985), (4096, 0.992),
                  (10240, 1.0)],
    # DCTCP WebSearch
    "websearch": [(1, 0.15), (4, 0.30), (16, 0.45), (64, 0.60), (256, 0.75),
                  (1024, 0.87), (4096, 0.95), (10240, 0.98), (30720, 1.0)],
    # Uniform small-flow debug workload
    "uniform": [(1, 0.0), (64, 1.0)],
}


@dataclass(frozen=True)
class WorkloadParams:
    workload: str = "fb_hadoop"
    load: float = 0.6              # offered load on the oversubscribed core
    incast_load: float = 0.0       # e.g. 0.05 -> "5% incast traffic"
    incast_degree: int = 100       # N-to-1
    incast_total_kb: int = 20480   # 20 MB aggregate per incast event
    locality: float = 0.0          # P(dst in same rack), App. B
    sigma: float = 2.0             # lognormal inter-arrival sigma
    mtu_kb: int = 1
    seed: int = 0


@dataclass
class FlowSet:
    """Static per-flow metadata baked into the jitted simulator step."""
    src: np.ndarray            # (F,) server id
    dst: np.ndarray            # (F,)
    size_pkts: np.ndarray      # (F,)
    arrival_tick: np.ndarray   # (F,)
    routes: np.ndarray         # (F, H) egress port ids, H = the fabric's hops
    ideal_fct: np.ndarray      # (F,) ticks
    fid: np.ndarray            # (F,) 32-bit flow ids (for hashing)
    is_incast: np.ndarray      # (F,) bool
    horizon: int = 0           # last arrival tick (for load accounting)

    @property
    def n_flows(self) -> int:
        return len(self.src)


def sample_sizes(rng: np.random.Generator, n: int, workload: str,
                 mtu_kb: int = 1) -> np.ndarray:
    pts = _SIZE_CDFS[workload]
    sizes_kb = np.array([p[0] for p in pts], float)
    cdf = np.array([p[1] for p in pts], float)
    u = rng.random(n)
    # log-linear interpolation of the inverse CDF; below the first point ->
    # first size.
    logs = np.interp(u, np.concatenate([[0.0], cdf]),
                     np.concatenate([[np.log(sizes_kb[0])], np.log(sizes_kb)]))
    kb = np.exp(logs)
    return np.maximum(1, np.round(kb / mtu_kb)).astype(np.int32)


def mean_size_pkts(workload: str, mtu_kb: int = 1, n: int = 200_000,
                   seed: int = 1234) -> float:
    rng = np.random.default_rng(seed)
    return float(sample_sizes(rng, n, workload, mtu_kb).mean())


def generate(topo: Topology, wp: WorkloadParams, n_flows: int,
             long_lived: int = 0, long_lived_pkts: int = 1 << 30) -> FlowSet:
    """Generate `n_flows` background flows (+ optional incast + long-lived).

    Load calibration: the core carries the share of traffic that crosses
    it (`params.core_share`: another ToR on the leaf-spine, another pod on
    a fat-tree) over `params.core_links` links; we scale the mean
    inter-arrival so that offered core load matches wp.load (paper's
    definition, §4 fn.4). Routes come from `topo.routes` (ECMP by flow id).
    """
    rng = np.random.default_rng(wp.seed)
    p = topo.params

    sizes = sample_sizes(rng, n_flows, wp.workload, wp.mtu_kb)

    # mean pkts/tick the network must carry to hit `load` on the core links:
    # core capacity = core_links * 1 pkt/tick; the share of flows crossing
    # the core = (1 - locality) * core_share.
    inter_frac = (1.0 - wp.locality) * p.core_share
    core_links = p.core_links
    target_core_pkts_per_tick = wp.load * core_links
    mean_size = float(sizes.mean())
    # flows/tick so that inter-rack bytes/tick hits the target
    lam = target_core_pkts_per_tick / (mean_size * max(inter_frac, 1e-6))

    # lognormal inter-arrivals with mean 1/lam, sigma=2 (heavy burst trains)
    sig = wp.sigma
    mu_ln = np.log(1.0 / lam) - 0.5 * sig * sig
    inter = rng.lognormal(mean=mu_ln, sigma=sig, size=n_flows)
    arrivals = np.cumsum(inter)
    arrivals = np.floor(arrivals).astype(np.int64)

    src = rng.integers(0, p.n_servers, n_flows)
    # destination: rack-local with prob locality, else uniform over others
    dst = rng.integers(0, p.n_servers, n_flows)
    same = dst == src
    dst[same] = (dst[same] + 1 + rng.integers(0, p.n_servers - 1, same.sum())) \
        % p.n_servers
    if wp.locality > 0:
        local = rng.random(n_flows) < wp.locality
        per = p.servers_per_rack
        rack = src // per
        off = rng.integers(1, per, local.sum())
        dst[local] = rack[local] * per + (src[local] % per + off) % per

    is_incast = np.zeros(n_flows, bool)
    horizon = int(arrivals.max()) if n_flows else 0

    # ---- incast injection ---------------------------------------------------
    if wp.incast_load > 0:
        per_flow_kb = max(1, wp.incast_total_kb // wp.incast_degree)
        per_event_pkts = wp.incast_degree * (per_flow_kb // wp.mtu_kb)
        # events/tick to consume incast_load of core capacity
        ev_rate = wp.incast_load * core_links / max(per_event_pkts, 1)
        n_events = max(1, int(np.floor(horizon * ev_rate)))
        ev_ticks = np.sort(rng.integers(0, max(horizon, 1), n_events))
        inc_src, inc_dst, inc_arr = [], [], []
        for t in ev_ticks:
            victim = int(rng.integers(0, p.n_servers))
            senders = rng.choice(
                np.setdiff1d(np.arange(p.n_servers), [victim]),
                size=min(wp.incast_degree, p.n_servers - 1), replace=False)
            inc_src.append(senders)
            inc_dst.append(np.full(len(senders), victim))
            inc_arr.append(np.full(len(senders), t))
        inc_src = np.concatenate(inc_src); inc_dst = np.concatenate(inc_dst)
        inc_arr = np.concatenate(inc_arr)
        inc_size = np.full(len(inc_src), per_flow_kb // wp.mtu_kb, np.int32)
        src = np.concatenate([src, inc_src])
        dst = np.concatenate([dst, inc_dst])
        sizes = np.concatenate([sizes, inc_size])
        arrivals = np.concatenate([arrivals, inc_arr])
        is_incast = np.concatenate([is_incast, np.ones(len(inc_src), bool)])

    # ---- long-lived flows (Table 1 / Fig. 5 experiments) --------------------
    if long_lived > 0:
        ll_src = rng.integers(0, p.n_servers, long_lived)
        ll_dst = (ll_src + p.servers_per_rack) % p.n_servers  # inter-rack
        src = np.concatenate([src, ll_src])
        dst = np.concatenate([dst, ll_dst])
        sizes = np.concatenate([sizes,
                                np.full(long_lived, long_lived_pkts, np.int64)])
        arrivals = np.concatenate([arrivals, np.zeros(long_lived, np.int64)])
        is_incast = np.concatenate([is_incast, np.zeros(long_lived, bool)])

    order = np.argsort(arrivals, kind="stable")
    src, dst = src[order], dst[order]
    sizes, arrivals, is_incast = sizes[order], arrivals[order], is_incast[order]

    fid = (np.arange(len(src), dtype=np.int64) * 2654435761 + wp.seed * 97 + 1) \
        % (1 << 31)
    fid = fid.astype(np.int32)
    routes = topo.routes(src, dst, fid)
    ideal = ideal_fct_ticks(routes, sizes.astype(np.int64), p.prop_ticks)

    return FlowSet(src=src.astype(np.int32), dst=dst.astype(np.int32),
                   size_pkts=sizes.astype(np.int32),
                   arrival_tick=arrivals.astype(np.int32), routes=routes,
                   ideal_fct=ideal.astype(np.int32), fid=fid,
                   is_incast=is_incast, horizon=horizon)
