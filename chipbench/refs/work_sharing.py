"""Plain reference of the work-sharing wave schedule.

An independent NumPy restatement of what the device program computes
for a ``work_sharing`` deployment: the hop graph of each architecture
(DTS, PRS-HAProxy, MSS) from the configuration's own constants, the
generation schedule, and one sequential pass per scan step in which
every shared resource serves its members first-in first-out.  It
imports nothing of the program under test.

The semantics, per step ``g`` (one generation of ``G`` messages per
producer):

* publish generation ``g``: message ``i`` of producer ``pr`` starts at
  the confirm of message ``i - W`` (if any) plus the client flush;
* deliver generation ``g - 1``: each queue hands its arrivals, in
  arrival order, round-robin to its consumers; a consumer's ``n``-th
  delivery waits for the ack of its ``(n - P)``-th;
* both legs walk their aligned hop paths slot by slot; at each slot a
  resource serves its members in order of the pilot lane's arrival
  (ties by member index), a pool giving the next member to its
  earliest-free server;
* consumers process in arrival order; acks flush every ``B``-th
  processed delivery and at the end of the generation.

Every lane follows the pilot lane's (lane 0's) order, as the stacked
seeds of one request do.  ``dtype`` sets the precision of every clock:
float64 is the configuration's; float32 is the control.
"""

from __future__ import annotations

import numpy as np

GBIT = 1e9

#: Limits of the comparison with the program (see PERF.md for the
#: readings each was set from): the widest gap of a per-message time as a
#: share of its result's simulated span, and of a summary throughput as a
#: share of the reference's.
LIMITS = {"consume_rel_gap": 1e-9, "publish_rel_gap": 2e-12,
          "throughput_rel_gap": 1e-9}

_SHARED = ("dsn_in", "dsn_out", "dsn_int", "tunnel", "dts_gw", "ttun")


# --------------------------------------------------------------------------
# Hop graph: resources and paths of each architecture
# --------------------------------------------------------------------------


def _el(res, lat=0.0, bf=1.0, xb=0):
    return [res, lat, bf, xb]


class Arch:
    """Resources ``{key: (kind, rate_Bps, servers, service_s, per_byte_s)}``
    and publish / delivery paths ``[[resource, latency, byte_factor,
    extra_bytes], ...]`` of one architecture in one deployment."""

    def __init__(self, name: str, cfg: dict) -> None:
        self.name = name
        c = self.c = cfg["calibration"]
        inv = cfg["inventory"]
        self.tenant_paths = name == "dts" and cfg["tenants"] > 1
        res = self.res = {}
        client_Bps = (inv["client_link_gbps"] * GBIT / 8.0
                      * c["client_link_eff"])
        for i in range(inv["n_producer_nodes"]):
            res[f"plink:{i}"] = ("pipe", client_Bps, 1, 0.0, 0.0)
        for i in range(inv["n_consumer_nodes"]):
            res[f"clink:{i}"] = ("pipe", client_Bps, 1, 0.0, 0.0)
        dsn_Bps = c["dsn_link_gbps"] * GBIT / 8.0
        int_Bps = c["dsn_internal_gbps"] * GBIT / 8.0
        for i in range(inv["n_dsn"]):
            res[f"dsn_in:{i}"] = ("pipe", dsn_Bps, 1, 0.0, 0.0)
            res[f"dsn_out:{i}"] = ("pipe", dsn_Bps, 1, 0.0, 0.0)
            res[f"dsn_int:{i}"] = ("pipe", int_Bps, 1, 0.0, 0.0)
            res[f"bcpu:{i}"] = ("pool", 0.0, c["broker_cpu_workers"], 0.0,
                                c["broker_per_byte_s"])
        if name == "dts" and self.tenant_paths:
            T = cfg["tenants"]
            infl = 1.0 + c["dts_tenant_gw_penalty"] * max(
                0, T - c["dts_tenant_gw_after"])
            gw = c["dts_gw_gbps"] * GBIT / 8.0
            for k in ("dts_gw_in", "dts_gw_out"):
                res[k] = ("pipe", gw, 1, c["dts_gw_service_s"] * infl, 0.0)
            for t in range(T):
                res[f"ttun:{t}"] = (
                    "pool", 0.0, 2, c["dts_tenant_tunnel_service_s"] * infl,
                    8.0 / (c["dts_tenant_tunnel_gbps"] * GBIT))
        elif name == "prs-haproxy":
            over = max(0, cfg["n_producers"] - c["haproxy_penalty_after"])
            res["tunnel"] = (
                "pipe", c["tunnel_gbps_haproxy"] * GBIT / 8.0, 1,
                c["tunnel_msg_service_s"]
                * (1.0 + c["haproxy_flow_penalty"] * over), 0.0)
            for k in ("pproxy", "cproxy"):
                res[k] = ("pool", 0.0, c["proxy_servers"],
                          c["proxy_msg_cpu_s"], 0.0)
        elif name == "mss":
            res["lb"] = ("pool", 0.0, c["lb_servers"], c["lb_service_s"], 0.0)
            res["ingress_in"] = ("pipe", c["ingress_gbps"] * GBIT / 8.0, 1,
                                 0.0, 0.0)
            res["ingress_out"] = ("pipe", c["ingress_out_gbps"] * GBIT / 8.0,
                                  1, c["ingress_out_msg_service_s"], 0.0)
            for d in ("in", "out"):
                for w in range(c["ingress_workers"]):
                    res[f"ingw_{d}:{w}"] = (
                        "pool", 0.0, 1, c["ingress_msg_cpu_s"],
                        1.0 / (c["ingress_worker_MBps"] * 1e6))
        elif name != "dts":
            raise ValueError(f"no reference hop graph for {name!r}")

    def tls(self, el: list) -> list:
        return [el[0], el[1] + self.c["tls_msg_cpu_s"],
                el[2] * self.c["tls_byte_factor"], el[3]]

    def flush_s(self) -> float:
        c = self.c
        return c["prs_client_flush_s"] if self.name == "prs-haproxy" \
            else c["client_flush_s"]

    def recv_latency_s(self, size: int) -> float:
        c = self.c
        small = (c["prs_small_msg_latency_s"] if self.name == "prs-haproxy"
                 else c["small_msg_latency_s"])
        return self.flush_s() + (small if size < c["small_msg_threshold"]
                                 else 0.0)

    def control_latency_s(self) -> float:
        c = self.c
        if self.name == "mss":
            return (c["control_latency_s"] + c["lb_latency_s"]
                    + c["mss_extra_latency_s"])
        return c["control_latency_s"]

    def _ingest(self, conn: int, home: int) -> list:
        c = self.c
        els = [_el(f"bcpu:{conn}", c["broker_publish_cpu_s"])]
        if home != conn:
            els += [_el(f"dsn_int:{conn}", c["intercluster_hop_s"]),
                    _el(f"bcpu:{home}", c["broker_publish_cpu_s"] * 0.5)]
        return els

    def _egress(self, home: int, conn: int) -> list:
        c = self.c
        els = [_el(f"bcpu:{home}", c["broker_deliver_cpu_s"])]
        if home != conn:
            els += [_el(f"dsn_int:{home}", c["intercluster_hop_s"]),
                    _el(f"bcpu:{conn}", c["broker_deliver_cpu_s"] * 0.5)]
        return els

    def publish_path(self, pnode: int, bnode: int, home: int,
                     tenant: int) -> list:
        c, fb = self.c, self.c["frame_bytes"]
        if self.name == "dts":
            if self.tenant_paths:
                return [self.tls(_el(f"plink:{pnode}", xb=fb)),
                        _el(f"ttun:{tenant}", c["proxy_latency_s"]),
                        self.tls(_el("dts_gw_in")),
                        _el(f"dsn_int:{bnode}")] + self._ingest(bnode, home)
            return [self.tls(_el(f"plink:{pnode}", xb=fb)),
                    self.tls(_el(f"dsn_in:{bnode}"))] + self._ingest(bnode,
                                                                     home)
        if self.name == "prs-haproxy":
            return [_el(f"plink:{pnode}", xb=fb),
                    _el("pproxy", c["proxy_latency_s"]),
                    self.tls(_el("tunnel")),
                    _el("cproxy", c["proxy_latency_s"]),
                    _el("dsn_int:1"),
                    _el(f"bcpu:{home}", c["broker_publish_cpu_s"])]
        worker = pnode % c["ingress_workers"]
        return [self.tls(_el(f"plink:{pnode}", xb=fb)),
                _el("lb", c["lb_latency_s"]),
                self.tls(_el(f"ingw_in:{worker}")),
                _el("ingress_in", c["mss_extra_latency_s"],
                    c["tls_byte_factor"], fb),
                _el(f"dsn_int:{home}"),
                _el(f"bcpu:{home}", c["broker_publish_cpu_s"])]

    def delivery_path(self, bnode: int, home: int, cnode: int,
                      tenant: int) -> list:
        c, fb = self.c, self.c["frame_bytes"]
        if self.name == "dts":
            els = self._egress(home, bnode)
            if self.tenant_paths:
                return els + [_el(f"dsn_int:{bnode}"),
                              self.tls(_el("dts_gw_out", xb=fb)),
                              _el(f"ttun:{tenant}", c["proxy_latency_s"]),
                              self.tls(_el(f"clink:{cnode}"))]
            return els + [self.tls(_el(f"dsn_out:{bnode}", xb=fb)),
                          self.tls(_el(f"clink:{cnode}"))]
        if self.name == "prs-haproxy":
            return self._egress(home, bnode) + [
                _el(f"dsn_out:{bnode}", xb=fb), _el(f"clink:{cnode}")]
        worker = cnode % c["ingress_workers"]
        return [_el(f"bcpu:{home}", c["broker_deliver_cpu_s"]),
                _el(f"dsn_int:{home}"),
                _el("ingress_out", c["mss_extra_latency_s"],
                    c["tls_byte_factor"], fb),
                self.tls(_el(f"ingw_out:{worker}")),
                _el("lb", c["lb_latency_s"]),
                self.tls(_el(f"clink:{cnode}", xb=fb))]

    def hold(self, el: list, size: int) -> float:
        kind, rate, _servers, svc, per_byte = self.res[el[0]]
        nbytes = size * el[2] + el[3]
        if kind == "pipe":
            return svc + (nbytes / rate if rate else 0.0)
        return svc + nbytes * per_byte


def _align(paths: dict) -> dict:
    """Pad each path between its longest common prefix and suffix of
    resource classes with empty slots, so shared hops share a slot."""
    def cls(el):
        return None if el is None else el[0].split(":", 1)[0]
    sigs = {k: [cls(e) for e in p] for k, p in paths.items()}
    n = min(len(s) for s in sigs.values())
    lcp = 0
    while lcp < n and len({s[lcp] for s in sigs.values()}) == 1:
        lcp += 1
    lcs = 0
    while lcs < n - lcp and len({s[-1 - lcs] for s in sigs.values()}) == 1:
        lcs += 1
    mid = max(len(s) - lcp - lcs for s in sigs.values())
    out = {}
    for k, p in paths.items():
        m = p[lcp:len(p) - lcs]
        out[k] = p[:lcp] + m + [None] * (mid - len(m)) + p[len(p) - lcs:]
    return out


# --------------------------------------------------------------------------
# Deployment topology and generation schedule
# --------------------------------------------------------------------------


class Plan:
    """Queues, placements, aligned hop paths and the generation size of
    one (configuration, architecture) pair."""

    def __init__(self, cfg: dict, arch: str) -> None:
        self.cfg = cfg
        a = self.arch = Arch(arch, cfg)
        inv, p = cfg["inventory"], cfg["params"]
        nP, nC, T = cfg["n_producers"], cfg["n_consumers"], cfg["tenants"]
        self.nP, self.nC = nP, nC
        self.size = size = cfg["workload"]["payload_bytes"]
        self.M = cfg["total_messages"] // nP
        self.W = max(2, min(p["confirm_window"], p["window_bytes"] // size))
        self.P, self.B = p["prefetch"], p["ack_batch"]
        self.proc_s = cfg["workload"]["consumer_proc_s"]
        ppt, cpt = max(1, nP // T), max(1, nC // T)
        if T > 1 and cfg["tenant_isolation"] == "vhost":
            nq_t = min(p["n_work_queues"], nC // T)
            self.nq = T * nq_t
            self.q_cons = [[t * cpt + j for j in range(cpt) if j % nq_t == qi]
                           for t in range(T) for qi in range(nq_t)]
            self.prod_q = [[(pr // ppt) * nq_t + qi for qi in range(nq_t)]
                           for pr in range(nP)]
        else:
            self.nq = min(p["n_work_queues"], nC)
            self.q_cons = [[c for c in range(nC) if c % self.nq == qi]
                           for qi in range(self.nq)]
            self.prod_q = [list(range(self.nq)) for _ in range(nP)]
        home = [q % inv["n_dsn"] for q in range(self.nq)]

        def t_of(i: int, per: int) -> int:
            return i // per if a.tenant_paths else 0

        pub = {(pr, q): a.publish_path(
            pr % inv["n_producer_nodes"], pr % inv["n_dsn"], home[q],
            t_of(pr, ppt)) for pr in range(nP) for q in self.prod_q[pr]}
        dels = {}
        for q, cs in enumerate(self.q_cons):
            dels.update({(q, c): p_ for c, p_ in _align({c: a.delivery_path(
                (c + 1) % inv["n_dsn"], home[q], c % inv["n_consumer_nodes"],
                t_of(c, cpt)) for c in cs}).items()})
        self.pub, self.dels = _align(pub), dels
        self.S = max(len(p_) for p_ in list(self.pub.values())
                     + list(dels.values()))
        self.G = min(self._generation(self._round(pub, dels, home)), self.M)
        self.nGen = -(-self.M // self.G)
        self.N = nP * self.G
        self.Np = 1 << max(0, self.N - 1).bit_length()

    def _round(self, pub: dict, dels: dict, home: list) -> int:
        """Messages per publish round: 8, or 2 where at most 64 clients
        share a saturated facility-side pipe (busiest shared resource at
        85% or more of the bottleneck, in busy seconds per message)."""
        if self.nP + self.nC > 64:
            return 8
        a, cost = self.arch, {}
        legs = [(p_, 1.0 / (self.nP * len(self.prod_q[pr])))
                for (pr, _q), p_ in pub.items()]
        legs += [(p_, 1.0 / (self.nq * len(self.q_cons[q])))
                 for (q, _c), p_ in dels.items()]
        for path, w in legs:
            for el in path:
                if el is None:
                    continue
                kind, _r, servers, _s, _b = a.res[el[0]]
                sec = a.hold(el, self.size)
                if kind == "pool":
                    sec /= max(1, servers)
                cost[el[0]] = cost.get(el[0], 0.0) + w * sec
        c_max = max(max(cost.values()), self.proc_s / max(1, self.nC))
        shared = max((v for k, v in cost.items() if k.startswith(_SHARED)),
                     default=0.0)
        return 2 if shared / c_max >= 0.85 else 8

    def _generation(self, rnd: int) -> int:
        """Largest G <= min(W, P/2, round) at which no consumer gets more
        than P/2 deliveries of one generation."""
        budget = max(1, self.P // 2)
        for G in range(min(self.W, budget, rnd), 0, -1):
            ok = True
            for q in range(self.nq):
                arrivals = sum(-(-G // len(self.prod_q[pr]))
                               for pr in range(self.nP)
                               if q in self.prod_q[pr])
                if -(-arrivals // len(self.q_cons[q])) > budget:
                    ok = False
                    break
            if ok:
                return G
        raise ValueError("no generation size fits this deployment")

    def rows(self, path: list) -> list:
        """Per slot ``(resource or None, hold, latency)``; short paths
        end in empty slots."""
        out = []
        for el in path + [None] * (self.S - len(path)):
            if el is None:
                out.append((None, 0.0, 0.0))
            else:
                out.append((el[0], self.arch.hold(el, self.size), el[1]))
        return out


# --------------------------------------------------------------------------
# The schedule
# --------------------------------------------------------------------------


def jitter_draws(plan: Plan, seeds: list, jitter: float) -> np.ndarray:
    """Service-time jitter ``(nGen, 2 * S + 1, Np, L)``: one uniform draw
    in ``[-j, j)`` per (generation, slot, member position) from each
    seed's own ``numpy.random.default_rng`` stream; slots ``[0, S)``
    publish, ``[S, 2S)`` delivery, ``2S`` consumer processing."""
    shape = (plan.nGen, 2 * plan.S + 1, plan.Np)
    raw = np.zeros(shape + (len(seeds),))
    if jitter:
        for lane, s in enumerate(seeds):
            raw[..., lane] = np.random.default_rng(s).uniform(
                -jitter, jitter, size=shape)
    return raw


def _fifo(order: list, arr: dict, hold: dict, start: np.ndarray) -> dict:
    """Serve ``order`` first-in first-out from a server free at
    ``start``; returns each member's finish time."""
    out, cur = {}, start
    for k in order:
        cur = np.maximum(arr[k], cur) + hold[k]
        out[k] = cur
    return out


def simulate(cfg: dict, arch: str, seeds: list,
             dtype: type = np.float64) -> dict:
    """Per-message ``consume`` and ``publish`` times ``(nP * M, L)`` of
    one stacked request cell (pilot lane = ``seeds[0]``), in message
    order ``producer * M + i``."""
    pl = Plan(cfg, arch)
    a = pl.arch
    L, S, G, M, W, P, B = (len(seeds), pl.S, pl.G, pl.M, pl.W, pl.P, pl.B)
    f = np.dtype(dtype).type
    raw = jitter_draws(pl, seeds, cfg["params"]["jitter"]).astype(dtype)
    flush, ctrl = f(a.flush_s()), f(a.control_latency_s())
    recv, proc_s = f(a.recv_latency_s(pl.size)), f(pl.proc_s)
    pub_rows = {k: [(r, f(h), f(lt)) for r, h, lt in pl.rows(p_)]
                for k, p_ in pl.pub.items()}
    del_rows = {k: [(r, f(h), f(lt)) for r, h, lt in pl.rows(p_)]
                for k, p_ in pl.dels.items()}
    zero = np.zeros(L, dtype=dtype)
    conf = np.zeros((pl.nP, W, L), dtype=dtype)
    ack = np.zeros((pl.nC, P, L), dtype=dtype)
    proc = [zero] * pl.nC
    free: dict = {}                  # (resource, side) -> server carries
    consume = np.full((pl.nP * M, L), np.nan, dtype=dtype)
    publish = np.full((pl.nP * M, L), np.nan, dtype=dtype)
    # round-robin cursors: deliveries so far per queue and per consumer
    cnt_q, cnt_c = [0] * pl.nq, [0] * pl.nC
    prev: list = []                  # last step's (m, pr, i, q, t_enq)

    for g in range(pl.nGen + 1):
        members = {}                 # combined index -> (rows, t, jit, side)
        pubs = []
        if g < pl.nGen:
            for m in range(pl.N):
                pr, i = m // G, g * G + m % G
                if i >= M:
                    continue
                qs = pl.prod_q[pr]
                q = qs[(pr + i) % len(qs)]
                gate = conf[pr, i % W] if i >= W else zero
                t0 = gate + flush
                publish[pr * M + i] = t0
                members[m] = (pub_rows[(pr, q)], t0, raw[g, :S, m], 0)
                pubs.append((m, pr, i, q))
        dels = {}
        if prev:
            by_q: dict = {}
            for m, pr, i, q, t_enq in sorted(
                    prev, key=lambda e: (e[3], e[4][0], e[0])):
                by_q.setdefault(q, []).append((m, pr, i, t_enq))
            for q, items in by_q.items():
                cs = pl.q_cons[q]
                k = len(cs)
                base_c = {c: cnt_c[c] for c in cs}
                for pos, (m, pr, i, t_enq) in enumerate(items):
                    c = cs[(cnt_q[q] + pos) % k]
                    n_on = base_c[c] + pos // k
                    dep = (np.maximum(t_enq, ack[c, n_on % P]) if n_on >= P
                           else t_enq)
                    members[pl.Np + m] = (del_rows[(q, c)], dep,
                                          raw[g - 1, S:2 * S, m], 1)
                    dels[m] = (pr, i, c, n_on)
                for pos in range(len(items)):
                    cnt_c[cs[(cnt_q[q] + pos) % k]] += 1
                cnt_q[q] += len(items)
        t = {k: v[1] for k, v in members.items()}
        for s in range(S):
            groups: dict = {}
            for k, (rows, _t0, jit, side) in members.items():
                r, h, lat = rows[s]
                if r is None:
                    t[k] = t[k] + lat
                else:
                    groups.setdefault((r, side), []).append(k)
            for (r, side), ks in groups.items():
                servers = a.res[r][2] if a.res[r][0] == "pool" else 1
                ks.sort(key=lambda k: (t[k][0], k))
                hold = {k: members[k][0][s][1] * (f(1.0) + members[k][2][s])
                        for k in ks}
                carry = sorted(free.get((r, side), [zero] * servers),
                               key=lambda x: x[0])
                out = {}
                for srv in range(servers):
                    mine = ks[srv::servers]
                    done = _fifo(mine, t, hold, carry[srv])
                    if mine:
                        carry[srv] = np.maximum(carry[srv], done[mine[-1]])
                    out.update(done)
                free[(r, side)] = carry
                for k in ks:
                    t[k] = out[k] + members[k][0][s][2]
        for m, pr, i, q in pubs:
            conf[pr, i % W] = t[m] + ctrl
        prev = [(m, pr, i, q, t[m]) for m, pr, i, q in pubs]
        if not dels:
            continue
        arr = {m: t[pl.Np + m] + recv for m in dels}
        hold = {m: proc_s * (f(1.0) + raw[g - 1, 2 * S, m]) for m in dels}
        by_c: dict = {}
        for m in sorted(dels, key=lambda m: (dels[m][2], arr[m][0], m)):
            by_c.setdefault(dels[m][2], []).append(m)
        for c, ms in by_c.items():
            seen = _fifo(ms, arr, hold, proc[c])
            proc[c] = np.maximum(proc[c], seen[ms[-1]])
            for pos, m in enumerate(ms):
                pr, i, _c, n_on = dels[m]
                consume[pr * M + i] = seen[m]
                nb = min(len(ms) - 1, pos + (B - 1 - pos % B))
                ack[c, n_on % P] = seen[ms[nb]] + ctrl
    return dict(consume=consume, publish=publish, G=G, nGen=pl.nGen)


def throughput(consume: np.ndarray, warmup_frac: float = 0.05) -> float:
    """Aggregate consumer rate (msgs/s of simulated time) past the first
    5% of completions: the paper's throughput."""
    ts = np.sort(consume)
    ts = ts[int(ts.size * warmup_frac):]
    return float((ts.size - 1) / (ts[-1] - ts[0]))


def hops_per_message(cfg: dict, arch: str) -> float:
    """Mean resource hops on a message's publish plus delivery path."""
    pl = Plan(cfg, arch)

    def mean(paths: dict) -> float:
        return sum(sum(el is not None for el in p) for p in paths.values()) \
            / len(paths)
    return mean(pl.pub) + mean(pl.dels)
