"""Plain reference of the tick-synchronous packet simulator (numpy only).

The benchmark decides `correct` by running this reference over the same
flows, fabric and protocol settings as the timed path, and comparing
every leaf of the final state, every emitted row and the summary metrics.
It imports nothing of the program and is written in another style: one
tick at a time, the arrivals of a tick processed one by one in ascending
order of the port they arrive from, every same-tick tie settled by
counters, and a packet's path read from its flow's route.

Semantics of one tick, in order:

1. derive: queue occupancy, per-switch buffer fill, the head packet's
   pause bit from the Bloom snapshot the upstream port holds, BFC's pause
   threshold ceil(pause_window / active queues), PFC hysteresis, and the
   flows that arrive at their source now.
2. control (every tau ticks): pop one to-be-resumed flow per paused
   queue that fell below the threshold, clear its pause and its upstream
   Bloom counters; then the Bloom pipeline moves one stage.
3. switch egress: each unblocked port sends the head of the first
   eligible queue in round-robin order from its pointer; a flow whose
   last packet leaves a hop frees its queue and its pause.
4. NIC: each server sends one packet of the first eligible flow in
   round-robin order from its pointer (window or token bucket gating).
5. arrivals: packets whose wire delay ends now are delivered (feedback
   scheduled) or enter the next switch: buffer admission, queue
   assignment (existing, first free, else a hashed random queue), ring
   capacity, ECN marking, enqueue, and BFC's pause when a queue exceeds
   the threshold. A dropped packet returns as a retransmit credit.
6. feedback: this tick's ACKs, marks and retransmit credits land, and
   the congestion-control law runs.
7. statistics: histograms every `stat_every` ticks and one emit row.

Float state is computed in `fdtype` (float32 as configured; the control
runs it in bfloat16). Where the device's compiler contracts a multiply
and an add into one rounding, or turns a division by a constant into a
multiplication by its reciprocal, `rules` says so (see float_rules.json),
so that the reference rounds where the device rounds.

The fabric is any port graph (`n_ports`, `n_servers`, `n_switches`,
`prop_ticks`, `switch_buffer_pkts`, `port_switch()`, `feeds()`), and the
hop width H is the width of the flows' `routes`: the per-hop tables are
(F, H), a packet leaves the fabric after hop H - 1 or where its route
ends, and the feedback rings hold H * prop_ticks + 2 rows.
"""
from __future__ import annotations

import numpy as np

from flowgen import hash_u32

BIG = 1 << 20
SUPPORTED_CC = ("none", "dcqcn")


class Reference:
    def __init__(self, fabric, config: dict, flows: dict,
                 rules: dict, fdtype=np.float32):
        self.fab = fabric
        self.pc = dict(config["proto"])
        self.tm = dict(config["timing"])
        self.sim = dict(config["sim"])
        self.rules = rules
        self.fd = fdtype
        pc = self.pc
        if pc["cc"] not in SUPPORTED_CC or pc["scheduler"] != "drr" \
                or pc["queue_key"] != "flow" or pc["source_signal"] \
                or pc["nic_sched"] != "drr" or pc["infinite_buffer"]:
            raise NotImplementedError(
                "the reference covers flow-keyed DRR switches with cc "
                f"in {SUPPORTED_CC}")
        self.f = {k: np.asarray(v) for k, v in flows.items()
                  if k != "horizon"}
        F = len(self.f["src"])
        P, NSRV, NSW = fabric.n_ports, fabric.n_servers, fabric.n_switches
        Q, CAP, PLCAP = pc["n_queues"], pc["queue_cap"], pc["pauselist_cap"]
        S, B = self.sim["bloom_stages"], self.sim["bloom_stage_bits"]
        self.F, self.P, self.Q, self.CAP, self.PLCAP = F, P, Q, CAP, PLCAP
        self.S, self.B = S, B
        self.PROP = fabric.prop_ticks
        self.routes = self.f["routes"].astype(np.int64)
        self.H = H = self.routes.shape[1]
        self.RING = H * self.PROP + 2
        self.RRING = self.tm["rto_ticks"] + 1
        self.hops = (self.routes >= 0).sum(axis=1)
        fid = self.f["fid"]
        self.fpos = np.stack([hash_u32(fid, s) % np.uint32(B)
                              for s in range(S)], axis=1).astype(np.int64)
        self.fbucket = (hash_u32(fid, 4)
                        % np.uint32(self.sim["ft_buckets"])).astype(np.int64)
        self.port_switch = fabric.port_switch()
        self.is_nic = self.port_switch < 0
        self.feeds = fabric.feeds()
        self.limit = fabric.switch_buffer_pkts

        fd = self.fd
        i64 = np.int64
        self.st = dict(
            t=0,
            rem_src=np.zeros(F, i64), sent=np.zeros(F, i64),
            acked=np.zeros(F, i64), delivered=np.zeros(F, i64),
            done=np.full(F, -1, i64),
            cwnd=np.full(F, pc["window_init"], fd),
            cwnd_ref=np.full(F, pc["window_init"], fd),
            rate=np.ones(F, fd), rate_target=np.ones(F, fd),
            tokens=np.ones(F, fd), alpha=np.zeros(F, fd),
            ack_seen=np.zeros(F, i64), mark_seen=np.zeros(F, i64),
            cc_timer=np.full(F, self.tm["e2e_rtt_ticks"], i64),
            since_dec=np.zeros(F, i64),
            qbuf=np.full((P, Q, CAP), -1, i64), qhead=np.zeros((P, Q), i64),
            qtail=np.zeros((P, Q), i64), qptr=np.zeros(P, i64),
            qsrf=np.full((P, Q), BIG, i64),
            f_q=np.full((F, H), -1, i64),
            f_cnt=np.zeros((F, H), i64),
            f_paused=np.zeros((F, H), bool),
            d_q=np.full((P, NSRV), -1, i64), d_cnt=np.zeros((P, NSRV), i64),
            bloom_counts=np.zeros((P, S, B), i64),
            bloom_mid=np.zeros((P, S, B), bool),
            bloom_rx=np.zeros((P, S, B), bool),
            pl=np.full((P, Q, PLCAP), -1, i64), pl_head=np.zeros((P, Q), i64),
            pl_tail=np.zeros((P, Q), i64),
            ing_occ=np.zeros(P, i64), pfc_paused=np.zeros(P, bool),
            wire_f=np.full((P, self.PROP), -1, i64),
            wire_hop=np.zeros((P, self.PROP), i64),
            tx_ewma=np.zeros(P, fd),
            ack_ring=np.zeros((self.RING, F), i64),
            mark_ring=np.zeros((self.RING, F), i64),
            u_ring=np.zeros((self.RING, F), fd),
            retx_ring=np.zeros((self.RRING, F), i64),
            sfc_ring=np.zeros((self.RING, F), i64),
            sfc_until=np.zeros(F, i64),
            nic_ptr=np.zeros(NSRV, i64),
            bucket_cnt=np.zeros((NSW, self.sim["ft_buckets"]), i64),
            stat_drops=0, stat_collisions=0, stat_allocs=0,
            stat_overflow=0, stat_pauses=0, stat_pfc_ticks=0,
            occ_hist=np.zeros(self.sim["occ_bins"], i64),
            flows_hist=np.zeros(self.sim["flows_bins"], i64),
            qlen_hist=np.zeros(self.sim["occ_bins"], i64),
        )
        self.emits = []

    # ---- float arithmetic as the device rounds it -------------------------

    def _mul_add(self, x, a: float, c, rule: str):
        """x * a + c, in one rounding where the device contracts it."""
        fd = self.fd
        if self.rules.get(rule) and fd == np.float32:
            return (x.astype(np.float64) * np.float64(fd(a))
                    + np.asarray(c, np.float64)).astype(fd)
        return (x * fd(a) + np.asarray(c, fd)).astype(fd)

    def _div_const(self, x, d: float):
        fd = self.fd
        if self.rules.get("ecn_div_by_reciprocal") and fd == np.float32:
            return (x * fd(1.0 / d)).astype(fd)
        return (x / fd(d)).astype(fd)

    def _hop_of(self, f, p) -> int:
        """Index of port p in flow f's route (0 where it is absent)."""
        hit = np.nonzero(self.routes[f] == p)[0]
        return int(hit[0]) if len(hit) else 0

    # ---- one tick -----------------------------------------------------------

    def step(self) -> None:
        st, pc, tm, fd = self.st, self.pc, self.tm, self.fd
        P, Q, CAP, PLCAP, F = self.P, self.Q, self.CAP, self.PLCAP, self.F
        routes, fpos = self.routes, self.fpos
        t = st["t"]
        probe = self.sim["probe_flow"]
        probe_delivered = int(st["delivered"][probe]) if probe >= 0 else 0
        p_ar = np.arange(P)
        s_ar = np.arange(self.S)

        # 1. derive
        occ = st["qtail"] - st["qhead"]
        port_occ = occ.sum(axis=1)
        sw_ports = ~self.is_nic
        sw_occ = np.bincount(self.port_switch[sw_ports],
                             weights=port_occ[sw_ports],
                             minlength=self.fab.n_switches).astype(np.int64)
        if pc["backpressure"]:
            head = st["qbuf"][p_ar[:, None], np.arange(Q)[None, :],
                              st["qhead"] % CAP]
            head_f = np.maximum(head >> 1, 0)
            got = st["bloom_rx"][p_ar[:, None, None], s_ar[None, None, :],
                                 fpos[head_f]]
            qpaused = got.all(axis=-1) & (occ > 0)
        else:
            qpaused = np.zeros((P, Q), bool)
        n_active = np.maximum(((occ > 0) & ~qpaused).sum(axis=1), 1)
        pw = tm["hrtt_ticks"] + tm["tau_ticks"]
        th = (pw + n_active - 1) // n_active
        if pc["pfc"]:
            free_buf = np.maximum(self.limit - sw_occ, 0)
            pfc_th = np.maximum(
                (np.float32(pc["pfc_frac"]) * free_buf.astype(np.float32))
                .astype(np.int64), 2)
            th_here = np.where(self.feeds >= 0,
                               pfc_th[np.maximum(self.feeds, 0)], 1 << 30)
            pfc = np.where(st["pfc_paused"], st["ing_occ"] > th_here // 2,
                           st["ing_occ"] > th_here)
        else:
            pfc = np.zeros(P, bool)
        newly = self.f["arrival_tick"] == t
        st["rem_src"] = st["rem_src"] + np.where(newly, self.f["size_pkts"], 0)

        # 2. control
        if pc["backpressure"]:
            is_tau = t % tm["tau_ticks"] == 0
            pops = (st["pl_tail"] > st["pl_head"]) & (occ < th[:, None])
            if pc["resume_limit"]:
                pops &= is_tau
            f_paused0 = st["f_paused"].copy()
            for p, q in zip(*np.nonzero(pops)):
                cand = int(st["pl"][p, q, st["pl_head"][p, q] % PLCAP])
                st["pl_head"][p, q] += 1
                if cand < 0:
                    continue
                h = self._hop_of(cand, p)
                if (st["f_q"][cand, h] == q and f_paused0[cand, h]
                        and st["f_cnt"][cand, h] > 0):
                    st["f_paused"][cand, h] = False
                    up = max(int(routes[cand, max(h - 1, 0)]), 0)
                    st["bloom_counts"][up, s_ar, fpos[cand]] -= 1
            if is_tau:
                st["bloom_rx"] = st["bloom_mid"]
                st["bloom_mid"] = st["bloom_counts"] > 0

        # 3. switch egress (each hop of a flow sits at one port, so the
        #    ports' updates never touch the same entry)
        elig = (occ > 0) & ~qpaused & ~pfc[:, None] & ~self.is_nic[:, None]
        can_tx = elig.any(axis=1)
        order = (np.arange(Q)[None, :] + st["qptr"][:, None]) % Q
        first = np.argmax(np.take_along_axis(elig, order, axis=1), axis=1)
        sel = np.where(can_tx, order[p_ar, first], 0)
        tx_entry = np.full(P, -1, np.int64)
        tx_hop = np.zeros(P, np.int64)
        occ_after = occ.copy()
        for p in np.nonzero(can_tx)[0]:
            q = sel[p]
            entry = int(st["qbuf"][p, q, st["qhead"][p, q] % CAP])
            f = max(entry >> 1, 0)
            h = self._hop_of(f, p)
            tx_entry[p], tx_hop[p] = entry, h
            st["qhead"][p, q] += 1
            st["qptr"][p] = q + 1
            occ_after[p, q] -= 1
            st["f_cnt"][f, h] -= 1
            up = max(int(routes[f, max(h - 1, 0)]), 0)
            departed = st["f_cnt"][f, h] == 0
            if departed:
                if pc["backpressure"]:
                    if st["f_paused"][f, h]:
                        st["bloom_counts"][up, s_ar, fpos[f]] -= 1
                    st["f_paused"][f, h] = False
                st["f_q"][f, h] = -1
                st["bucket_cnt"][max(self.port_switch[p], 0),
                                 self.fbucket[f]] -= 1
            if h > 0:
                st["ing_occ"][up] -= 1
            if occ_after[p, q] == 0:
                st["qsrf"][p, q] = BIG
        st["tx_ewma"] = self._mul_add(st["tx_ewma"], 1 - 1 / 32,
                                      can_tx.astype(fd) / fd(32),
                                      "ewma_fma")

        # 4. NIC
        rate_proto = pc["cc"] == "dcqcn"
        first_port = routes[:, 0]
        avail = ((self.f["arrival_tick"] <= t) & (st["rem_src"] > 0)
                 & (st["done"] < 0))
        if pc["backpressure"]:
            nic_paused = st["bloom_rx"][first_port[:, None], s_ar[None, :],
                                        fpos].all(axis=-1)
        else:
            nic_paused = np.zeros(F, bool)
        ok = avail & ~nic_paused & ~pfc[first_port]
        if rate_proto:
            st["tokens"] = np.minimum(st["tokens"] + st["rate"], fd(2.0))
            ok &= st["tokens"] >= fd(1.0)
        cand = np.nonzero(ok)[0]
        srv = self.f["src"][cand].astype(np.int64)
        # round robin: the first eligible flow at or after the pointer,
        # else the first eligible flow of the server
        key = cand + (cand < st["nic_ptr"][srv]) * (F + 1)
        picks = {}
        for s, k, f in zip(srv.tolist(), key.tolist(), cand.tolist()):
            if s not in picks or k < picks[s][0]:
                picks[s] = (k, f)
        nic_tx = np.zeros(self.fab.n_servers, bool)
        nic_sel = np.zeros(self.fab.n_servers, np.int64)
        for s, (_, f) in picks.items():
            nic_tx[s], nic_sel[s] = True, f
            st["rem_src"][f] -= 1
            st["sent"][f] += 1
            if rate_proto:
                st["tokens"][f] = st["tokens"][f] - fd(1.0)
            st["nic_ptr"][s] = f + 1
        nsrv = self.fab.n_servers
        st["tx_ewma"][:nsrv] = (st["tx_ewma"][:nsrv]
                                + nic_tx.astype(fd) / fd(32)).astype(fd)

        # 5. wires and arrivals
        slot = t % self.PROP
        arr_entry = st["wire_f"][:, slot].copy()
        arr_hop = st["wire_hop"][:, slot].copy()
        new_entry = np.where(can_tx, tx_entry, -1)
        new_hop = np.where(can_tx, tx_hop, 0)
        new_entry[:nsrv] = np.where(nic_tx, nic_sel * 2, new_entry[:nsrv])
        st["wire_f"][:, slot] = new_entry
        st["wire_hop"][:, slot] = new_hop

        f_cnt0 = st["f_cnt"].copy()
        f_paused0 = st["f_paused"].copy()
        bucket0 = st["bucket_cnt"].copy()
        pl_tail0 = st["pl_tail"].copy()
        free = occ_after == 0
        n_free = free.sum(axis=1)
        # per-arrival hashes and marking probabilities, drawn up front
        a_f = np.maximum(arr_entry >> 1, 0)
        fid_u = self.f["fid"][a_f].astype(np.uint32)
        t_u = np.uint32(t % (1 << 32))
        q_rand = (hash_u32(fid_u + t_u, 3) % np.uint32(Q)).astype(np.int64)
        q_hash = (hash_u32(fid_u, 2) % np.uint32(Q)).astype(np.int64)
        if pc["ecn"]:
            frac = np.clip(self._div_const(
                (port_occ - pc["ecn_kmin"]).astype(np.float32),
                max(pc["ecn_kmax"] - pc["ecn_kmin"], 1)),
                np.float32(0), np.float32(1))
            rnd = (hash_u32(fid_u ^ t_u, 1).astype(np.float32)
                   / np.float32(2 ** 32))
        sw_seen = {}            # switch -> arrivals so far this tick
        alloc_seen = {}         # port -> arrivals that needed a queue
        ring_seen = {}          # (port, queue) -> arrivals so far
        enq_seen = {}           # (port, queue) -> accepted so far
        accepted = []           # (upstream port, flow, hop, port, queue)
        drops = collisions = allocs = overflow = 0
        for u in np.nonzero(arr_entry >= 0)[0].tolist():
            entry = int(arr_entry[u])
            f, mark = entry >> 1, entry & 1
            hop = int(arr_hop[u])
            nh = min(hop + 1, self.H - 1)
            nxt = int(routes[f, nh])
            if hop + 1 >= self.H or nxt < 0:
                st["delivered"][f] += 1
                if (st["delivered"][f] >= self.f["size_pkts"][f]
                        and st["done"][f] < 0):
                    st["done"][f] = t
                fb = (t + self.hops[f] * self.PROP + 1) % self.RING
                st["ack_ring"][fb, f] += 1
                if mark:
                    st["mark_ring"][fb, f] += 1
                continue
            p = nxt
            sw = int(self.port_switch[p])
            room = sw_occ[sw] + sw_seen.get(sw, 0) < self.limit
            sw_seen[sw] = sw_seen.get(sw, 0) + 1
            have = f_cnt0[f, nh] > 0
            if have:
                q = max(int(st["f_q"][f, nh]), 0)
            else:
                allocs += 1
                if pc["dynamic_queues"]:
                    r = alloc_seen.get(p, 0)
                    alloc_seen[p] = r + 1
                    if r < n_free[p]:
                        q = int(np.nonzero(free[p])[0][r])
                    else:
                        q = int(q_rand[u])
                        collisions += 1
                else:
                    q = int(q_hash[u])
                    collisions += int(occ_after[p, q] > 0)
            r = ring_seen.get((p, q), 0)
            ring_seen[(p, q)] = r + 1
            accept = room and occ_after[p, q] + r < CAP
            if pc["ecn"]:
                mark = max(mark, int(rnd[u] < frac[p]))
            if not accept:
                drops += 1
                st["retx_ring"][(t + tm["rto_ticks"]) % self.RRING, f] += 1
                continue
            k = enq_seen.get((p, q), 0)
            enq_seen[(p, q)] = k + 1
            st["qbuf"][p, q, (st["qtail"][p, q] + k) % CAP] = f * 2 + mark
            if f_cnt0[f, nh] == 0:
                b = self.fbucket[f]
                overflow += int(bucket0[sw, b] >= self.sim["ft_bucket_size"])
                st["bucket_cnt"][sw, b] += 1
            st["f_cnt"][f, nh] += 1
            st["f_q"][f, nh] = q
            st["ing_occ"][u] += 1
            accepted.append((u, f, nh, p, q))
        for (p, q), k in enq_seen.items():
            st["qtail"][p, q] += k
        occ_new = st["qtail"] - st["qhead"]

        pauses = 0
        if pc["backpressure"]:
            pushed = {}
            for u, f, nh, p, q in accepted:
                if (occ_new[p, q] > th[p] and not f_paused0[f, nh]
                        and pl_tail0[p, q] - st["pl_head"][p, q]
                        < PLCAP - 32):
                    st["f_paused"][f, nh] = True
                    st["bloom_counts"][u, s_ar, fpos[f]] += 1
                    k = pushed.get((p, q), 0)
                    pushed[(p, q)] = k + 1
                    st["pl"][p, q, (pl_tail0[p, q] + k) % PLCAP] = f
                    pauses += 1
            for (p, q), k in pushed.items():
                st["pl_tail"][p, q] += k

        # 6. feedback
        row = t % self.RING
        acks = st["ack_ring"][row].copy()
        marks = st["mark_ring"][row].copy()
        st["ack_ring"][row] = 0
        st["mark_ring"][row] = 0
        st["u_ring"][row] = 0
        st["acked"] += acks
        rrow = t % self.RRING
        retx = st["retx_ring"][rrow].copy()
        st["retx_ring"][rrow] = 0
        st["rem_src"] += retx
        st["sent"] -= retx
        self._cc(acks, marks)

        # 7. statistics
        sim = self.sim
        if t % sim["stat_every"] == 0:
            ob = np.clip(sw_occ * sim["occ_bins"] // max(self.limit, 1), 0,
                         sim["occ_bins"] - 1)
            np.add.at(st["occ_hist"], ob, 1)
            act = (st["f_cnt"] > 0) & (routes >= 0)
            per_port = np.bincount(routes[act], minlength=P)
            fb = np.clip(per_port, 0, sim["flows_bins"] - 1)
            np.add.at(st["flows_hist"], fb[~self.is_nic], 1)
            nz = occ_new > 0
            qb = np.clip(occ_new[nz] * sim["occ_bins"] // max(CAP, 1), 0,
                         sim["occ_bins"] - 1)
            np.add.at(st["qlen_hist"], qb, 1)
        st["stat_drops"] += drops
        st["stat_collisions"] += collisions
        st["stat_allocs"] += allocs
        st["stat_overflow"] += overflow
        st["stat_pauses"] += pauses
        st["stat_pfc_ticks"] += int(pfc.sum())
        st["pfc_paused"] = pfc
        self.emits.append((int(sw_occ.max()), int(pfc.sum()),
                           probe_delivered))
        st["t"] = t + 1

    def _cc(self, acks, marks) -> None:
        st, pc, tm, fd = self.st, self.pc, self.tm, self.fd
        st["ack_seen"] = st["ack_seen"] + acks
        st["mark_seen"] = st["mark_seen"] + marks
        st["cc_timer"] = st["cc_timer"] - 1
        if pc["cc"] != "dcqcn":
            return
        rate, target, alpha = st["rate"], st["rate_target"], st["alpha"]
        epoch = st["cc_timer"] <= 0
        cong = st["mark_seen"] > 0
        cut = epoch & cong
        target = np.where(cut, rate, target)
        rate = np.where(cut, rate * (fd(1) - alpha / fd(2)), rate).astype(fd)
        g = pc["dcqcn_alpha_g"]
        decayed = (alpha * fd(1 - g)).astype(fd)
        raised = self._mul_add(alpha, 1 - g, fd(g), "alpha_fma")
        alpha = np.where(epoch, np.where(cong, raised, decayed), alpha)
        since = np.where(cut, 0, st["since_dec"] + 1)
        inc = since >= pc["dcqcn_timer"]
        rate = np.where(inc, (rate + target) / fd(2), rate).astype(fd)
        target = np.where(inc, np.minimum(target + fd(pc["dcqcn_rai"]),
                                          fd(1.0)), target).astype(fd)
        since = np.where(inc, 0, since)
        st["rate"] = np.clip(rate, fd(1e-3), fd(1.0)).astype(fd)
        st["rate_target"] = target
        st["alpha"] = alpha.astype(fd)
        st["since_dec"] = since
        st["mark_seen"] = np.where(epoch, 0, st["mark_seen"])
        st["ack_seen"] = np.where(epoch, 0, st["ack_seen"])
        st["cc_timer"] = np.where(epoch, tm["e2e_rtt_ticks"], st["cc_timer"])

    def run(self, n_ticks: int):
        for _ in range(n_ticks):
            self.step()
        return self.st, np.asarray(self.emits, np.int64).reshape(-1, 3)


def simulate(fabric, config: dict, flows: dict, n_ticks: int,
             rules: dict, fdtype=np.float32):
    """Final state (with the feedback rings in offset-from-now order) and
    the (n_ticks, 3) emit rows of one lane."""
    ref = Reference(fabric, config, flows, rules, fdtype)
    st, emits = ref.run(n_ticks)
    t = st["t"]
    for name in ("ack_ring", "mark_ring", "u_ring", "sfc_ring"):
        ring = st[name]
        st[name] = ring[(t + np.arange(ring.shape[0])) % ring.shape[0]]
    return st, emits


def summarize(st: dict, emits: np.ndarray, flows: dict, n_links: int):
    """The summary a user reads from one lane: FCT slowdowns of the
    finished background flows, buffer and PFC shares, event counters."""
    done = np.asarray(st["done"])
    incast = np.asarray(flows["is_incast"])
    mask = (done >= 0) & ~incast
    fct = (done - flows["arrival_tick"]).astype(np.float64)
    slow = (fct / np.maximum(flows["ideal_fct"], 1))[mask]

    def pct(x, q):
        return float(np.percentile(x, q)) if len(x) else float("nan")

    occ = emits[:, 0]
    return dict(
        completed=int(mask.sum()), total=int((~incast).sum()),
        fct_slowdown_avg=float(slow.mean()) if len(slow) else float("nan"),
        fct_slowdown_p50=pct(slow, 50), fct_slowdown_p95=pct(slow, 95),
        fct_slowdown_p99=pct(slow, 99),
        buffer_p99_pkts=pct(occ, 99),
        buffer_max_pkts=int(occ.max()) if len(occ) else 0,
        pfc_pause_frac=float(emits[:, 1].sum())
        / max(len(emits) * n_links, 1),
        drops=int(st["stat_drops"]), collisions=int(st["stat_collisions"]),
        allocs=int(st["stat_allocs"]), overflow=int(st["stat_overflow"]),
        pauses=int(st["stat_pauses"]))
