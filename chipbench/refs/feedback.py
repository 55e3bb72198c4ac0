"""Plain reference of the feedback wave schedule.

An independent NumPy restatement of what the device program computes
for a ``feedback`` deployment: work sharing in which every consumed
message is answered by a reply, published by its consumer to its
producer's reply queue and delivered back to the producer.  It imports
nothing of the program under test; the hop graphs, the alignment and
the FIFO serve are those of the work-sharing reference beside it
(``work_sharing.py``), extended by the reply paths.

The semantics, per scan step ``k`` (publishes and deliveries as in work
sharing: generation ``k`` is published, generation ``k - 1`` delivered):

* the publishes and deliveries take their first hop alone;
* then replies leave the pending-reply buffer: a reply is published
  once its consumer's processing end is no later than the latest
  publish past its first hop less the cohort horizon, or once the
  ingress resources it shares with the publishes are idle by then and
  the latest delivery past its first hop has reached it; a reply is
  delivered once its departure from its producer's reply queue is no
  later than that latest delivery.  A step without publishes (or
  without deliveries) takes every reply of that kind.  At most ``Np``
  of each go, earliest pilot-lane time first (ties by message);
* a reply's departure is its arrival at the reply queue, past the
  producer's prefetch window the later of that and the producer's ack
  of its reply ``n - P``; a reply whose ack ``n - P`` has not been
  taken waits;
* the replies take their first hop, then all four legs walk their other
  hops together: at each hop a resource serves its members in order of
  the pilot lane's arrival (ties by position: publishes, deliveries,
  reply-publishes, reply-deliveries), one chain per direction, except a
  link that every path of a request leg and every path of the reply
  leg of the other direction cross (the PRS tunnel), which is one;
* consumers process and ack as in work sharing; each reply published
  is numbered in its producer's reply queue in arrival order; each
  reply received is acked in batches of ``B`` per producer, flushed at
  the end of the step;
* after the ``nGen + 1`` steps of the request legs come
  ``nGen + ceil(M / P) + 1`` drain steps.

Every lane follows the pilot lane's (lane 0's) order.  ``dtype`` sets
the precision of every clock: float64 is the configuration's; float32
is the control.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _work_sharing():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "work_sharing.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_feedback_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ws = _work_sharing()
_el, _align, GBIT = ws._el, ws._align, ws.GBIT

#: Limits of the comparison with the program (see PERF.md for the
#: readings each was set from): the work-sharing reference's, but for
#: consume times, whose float32 control reads far higher here (a flipped
#: reply admission moves whole replies), so that the limit keeps more
#: room above the program's readings than below the control's.
LIMITS = dict(ws.LIMITS, consume_rel_gap=1e-8)


class Arch(ws.Arch):
    """The work-sharing hop graph plus the reply legs: client NICs are
    full duplex (``clink_tx`` carries a consumer's replies, ``plink_rx``
    a producer's); a reply mirrors the publish / delivery path, except
    PRS, whose consumers reply inside the facility and whose replies
    re-cross the tunnel to the producers."""

    def __init__(self, name: str, cfg: dict) -> None:
        super().__init__(name, cfg)
        inv, c = cfg["inventory"], self.c
        client_Bps = (inv["client_link_gbps"] * GBIT / 8.0
                      * c["client_link_eff"])
        for i in range(inv["n_producer_nodes"]):
            self.res[f"plink_rx:{i}"] = ("pipe", client_Bps, 1, 0.0, 0.0)
        for i in range(inv["n_consumer_nodes"]):
            self.res[f"clink_tx:{i}"] = ("pipe", client_Bps, 1, 0.0, 0.0)

    @staticmethod
    def _swap(path: list, frm: str, to: str) -> list:
        return [[to + el[0][len(frm):] if el[0].startswith(frm) else el[0]]
                + el[1:] for el in path]

    def reply_publish_path(self, cnode: int, bnode: int, home: int) -> list:
        if self.name == "prs-haproxy":
            return [_el(f"clink_tx:{cnode}", xb=self.c["frame_bytes"]),
                    _el(f"dsn_in:{bnode}")] + self._ingest(bnode, home)
        return self._swap(self.publish_path(cnode, bnode, home, 0),
                          "plink:", "clink_tx:")

    def reply_delivery_path(self, home: int, bnode: int, pnode: int) -> list:
        c = self.c
        if self.name == "prs-haproxy":
            return [_el(f"bcpu:{home}", c["broker_deliver_cpu_s"]),
                    _el(f"dsn_int:{home}"),
                    _el("cproxy", c["proxy_latency_s"]),
                    self.tls(_el("tunnel")),
                    _el("pproxy", c["proxy_latency_s"]),
                    _el(f"plink_rx:{pnode}", xb=c["frame_bytes"])]
        return self._swap(self.delivery_path(bnode, home, pnode, 0),
                          "clink:", "plink_rx:")


def _resources(paths) -> list:
    return [{el[0] for el in p if el is not None} for p in paths]


class Plan(ws.Plan):
    """Queues, placements, aligned hop paths of all four legs, the
    generation size, the scan's length and the cohort horizon of one
    (configuration, architecture) pair."""

    def __init__(self, cfg: dict, arch: str) -> None:
        if cfg["tenants"] != 1:
            raise ValueError("the feedback reference takes one tenant")
        self.cfg = cfg
        a = self.arch = Arch(arch, cfg)
        inv, p = cfg["inventory"], cfg["params"]
        nP, nC = self.nP, self.nC = cfg["n_producers"], cfg["n_consumers"]
        self.size = size = cfg["workload"]["payload_bytes"]
        self.rsize = max(1, int(size * p.get("reply_factor", 1.0)))
        self.M = cfg["total_messages"] // nP
        self.W = max(2, min(p["confirm_window"], p["window_bytes"] // size))
        self.P, self.B = p["prefetch"], p["ack_batch"]
        self.proc_s = cfg["workload"]["consumer_proc_s"]
        self.nq = nq = min(p["n_work_queues"], nC)
        self.q_cons = [[c for c in range(nC) if c % nq == q]
                       for q in range(nq)]
        self.prod_q = [list(range(nq)) for _ in range(nP)]
        n_dsn = inv["n_dsn"]
        home = [q % n_dsn for q in range(nq)]
        rhome = [(nq + pr) % n_dsn for pr in range(nP)]
        pnode = [pr % inv["n_producer_nodes"] for pr in range(nP)]
        cnode = [c % inv["n_consumer_nodes"] for c in range(nC)]
        pub = {(pr, q): a.publish_path(pnode[pr], pr % n_dsn, home[q], 0)
               for pr in range(nP) for q in self.prod_q[pr]}
        dels = {}
        for q, cs in enumerate(self.q_cons):
            dels.update({(q, c): p_ for c, p_ in _align({
                c: a.delivery_path((c + 1) % n_dsn, home[q], cnode[c], 0)
                for c in cs}).items()})
        rps = {(c, pr): a.reply_publish_path(cnode[c], (c + 1) % n_dsn,
                                             rhome[pr])
               for pr in range(nP)
               for c in sorted({c for q in self.prod_q[pr]
                                for c in self.q_cons[q]})}
        self.pub, self.dels, self.rps = _align(pub), dels, _align(rps)
        self.rds = {pr: a.reply_delivery_path(rhome[pr], pr % n_dsn,
                                              pnode[pr]) for pr in range(nP)}
        legs = (self.pub, self.dels, self.rps, self.rds)
        self.S = max(len(p_) for leg in legs for p_ in leg.values())
        # the cohort engines' round and horizon, cut where at most 64
        # clients share a saturated facility-side pipe
        saturated = nP + nC <= 64 and self._utilization(rhome) >= 0.85
        self.G = min(self._generation(2 if saturated else 8), self.M)
        self.horizon = max(1e-3, 1e-3 * (nP + nC) / 16.0) * (
            0.25 if saturated else 1.0)
        self.nGen = -(-self.M // self.G)
        self.N = nP * self.G
        self.Np = 1 << max(0, self.N - 1).bit_length()
        self.drain = self.nGen + -(-self.M // self.P) + 1
        self.nSteps = self.nGen + 1 + self.drain
        # one chain for both directions: a pipe on every path of a
        # request leg that every path of the reply leg of the other
        # direction crosses, and the other request leg never
        every = [set.intersection(*_resources(leg.values())) for leg in legs]
        anyp = [set.union(*_resources(leg.values())) for leg in legs]
        pipes = {k for k, r in a.res.items() if r[0] == "pipe"}
        self.merged = pipes & (((every[3] & every[0]) - anyp[1])
                               | ((every[2] & every[1]) - anyp[0]))
        # the ingress resources replies share with the publishes
        self.shared = anyp[2] & anyp[0]

    def _utilization(self, rhome: list) -> float:
        """Busiest shared facility-side pipe over the bottleneck, in
        busy seconds per consumed message, all four legs counted."""
        a, nP, nC, cost = self.arch, self.nP, self.nC, {}
        legs = [(p_, 1.0 / (nP * len(self.prod_q[pr])), self.size)
                for (pr, _q), p_ in self.pub.items()]
        legs += [(p_, 1.0 / (self.nq * len(self.q_cons[q])), self.size)
                 for (q, _c), p_ in self.dels.items()]
        weight: dict = {}
        for pr in range(nP):
            weight[rhome[pr]] = weight.get(rhome[pr], 0.0) + 1.0 / nP
        inv = self.cfg["inventory"]
        for c in range(nC):
            for h, w in weight.items():
                legs.append((a.reply_publish_path(
                    c % inv["n_consumer_nodes"], (c + 1) % inv["n_dsn"], h),
                    w / nC, self.rsize))
        legs += [(p_, 1.0 / nP, self.rsize) for p_ in self.rds.values()]
        for path, w, size in legs:
            for el in path:
                if el is None:
                    continue
                kind, _r, servers, _s, _b = a.res[el[0]]
                sec = a.hold(el, size)
                if kind == "pool":
                    sec /= max(1, servers)
                cost[el[0]] = cost.get(el[0], 0.0) + w * sec
        c_max = max(max(cost.values()), self.proc_s / max(1, nC))
        shared = max((v for k, v in cost.items()
                      if k.startswith(ws._SHARED)), default=0.0)
        return shared / c_max

    def rows(self, path: list, size: int = 0) -> list:
        out = []
        for el in path + [None] * (self.S - len(path)):
            if el is None:
                out.append((None, 0.0, 0.0))
            else:
                out.append((el[0], self.arch.hold(el, size or self.size),
                            el[1]))
        return out


def jitter_draws(plan: Plan, seeds: list, jitter: float) -> np.ndarray:
    """Service-time jitter ``(nGen, 4 * S + 1, Np, L)``: slots
    ``[0, S)`` publish, ``[S, 2S)`` delivery, ``2S`` consumer
    processing, ``[2S + 1, 3S + 1)`` reply-publish and ``[3S + 1,
    4S + 1)`` reply-delivery of the message at (generation, member)."""
    shape = (plan.nGen, 4 * plan.S + 1, plan.Np)
    raw = np.zeros(shape + (len(seeds),))
    if jitter:
        for lane, s in enumerate(seeds):
            raw[..., lane] = np.random.default_rng(s).uniform(
                -jitter, jitter, size=shape)
    return raw


def _serve(pl: Plan, members: dict, t: dict, lo: int, hi: int,
           free: dict, f: type) -> None:
    """Walk ``members`` (combined index -> (rows, jitter per slot,
    direction)) through slots ``[lo, hi)``, advancing their times ``t``
    and the chains' carries ``free``."""
    if not members:
        return
    a = pl.arch
    zero = np.zeros_like(next(iter(t.values())))
    for s in range(lo, hi):
        groups: dict = {}
        for k, (rows, _jit, side) in members.items():
            r, _h, lat = rows[s]
            if r is None:
                t[k] = t[k] + lat
            else:
                groups.setdefault((r, 0 if r in pl.merged else side),
                                  []).append(k)
        for (r, side), ks in groups.items():
            servers = a.res[r][2] if a.res[r][0] == "pool" else 1
            ks.sort(key=lambda k: (t[k][0], k))
            hold = {k: members[k][0][s][1] * (f(1.0) + members[k][1][s])
                    for k in ks}
            carry = sorted(free.get((r, side), [zero] * servers),
                           key=lambda x: x[0])
            out = {}
            for srv in range(servers):
                mine = ks[srv::servers]
                done = ws._fifo(mine, t, hold, carry[srv])
                if mine:
                    carry[srv] = np.maximum(carry[srv], done[mine[-1]])
                out.update(done)
            free[(r, side)] = carry
            for k in ks:
                t[k] = out[k] + members[k][0][s][2]


def _first(pending: dict, n: int) -> list:
    """Up to ``n`` rows of ``pending`` (row -> pilot time), earliest
    first, ties by row."""
    return sorted(pending, key=lambda row: (pending[row], row))[:n]


def simulate(cfg: dict, arch: str, seeds: list,
             dtype: type = np.float64) -> dict:
    """Per-message ``consume``, ``publish`` and ``rtt`` times
    ``(nP * M, L)`` of one stacked request cell (pilot lane =
    ``seeds[0]``), in message order ``producer * M + i``; a reply never
    received reads ``inf``."""
    pl = Plan(cfg, arch)
    a = pl.arch
    L, S, G, M, W, P, B, Np = (len(seeds), pl.S, pl.G, pl.M, pl.W, pl.P,
                               pl.B, pl.Np)
    f = np.dtype(dtype).type
    raw = jitter_draws(pl, seeds, cfg["params"]["jitter"]).astype(dtype)
    flush, ctrl = f(a.flush_s()), f(a.control_latency_s())
    recv, proc_s = f(a.recv_latency_s(pl.size)), f(pl.proc_s)
    recv_rep = f(a.recv_latency_s(pl.rsize))
    horizon, inf = f(pl.horizon), f(np.inf)

    def typed(rows: list) -> list:
        return [(r, f(h), f(lt)) for r, h, lt in rows]
    pub_rows = {k: typed(pl.rows(p_)) for k, p_ in pl.pub.items()}
    del_rows = {k: typed(pl.rows(p_)) for k, p_ in pl.dels.items()}
    rp_rows = {k: typed(pl.rows(p_, pl.rsize)) for k, p_ in pl.rps.items()}
    rd_rows = {k: typed(pl.rows(p_, pl.rsize)) for k, p_ in pl.rds.items()}
    zero = np.zeros(L, dtype=dtype)
    conf = np.zeros((pl.nP, W, L), dtype=dtype)
    ack = np.zeros((pl.nC, P, L), dtype=dtype)
    proc = [zero] * pl.nC
    free: dict = {}                  # (resource, side) -> server carries
    consume = np.full((pl.nP * M, L), np.nan, dtype=dtype)
    publish = np.full((pl.nP * M, L), np.nan, dtype=dtype)
    back = np.full((pl.nP * M, L), np.inf, dtype=dtype)
    cnt_q, cnt_c = [0] * pl.nq, [0] * pl.nC
    prev: list = []                  # last step's (m, pr, i, q, t_enq)
    rp_wait: dict = {}               # row -> (consumer's end, consumer)
    rd_wait: dict = {}               # row -> (arrival at reply queue, n)
    cnt_p = [0] * pl.nP
    prep = np.zeros((pl.nP, P, L), dtype=dtype)
    ptag = np.full((pl.nP, P), -1)
    peak = 0

    def reply_jit(row: int, lo: int) -> np.ndarray:
        pr, i = divmod(row, M)
        return raw[i // G, lo:lo + S, pr * G + i % G]

    for k in range(pl.nSteps):
        members: dict = {}           # combined index -> (rows, jit, side)
        t: dict = {}
        pubs = []
        if k < pl.nGen:
            for m in range(pl.N):
                pr, i = m // G, k * G + m % G
                if i >= M:
                    continue
                qs = pl.prod_q[pr]
                q = qs[(pr + i) % len(qs)]
                gate = conf[pr, i % W] if i >= W else zero
                t[m] = gate + flush
                publish[pr * M + i] = t[m]
                members[m] = (pub_rows[(pr, q)], raw[k, :S, m], 0)
                pubs.append((m, pr, i, q))
        dels = {}
        if prev:
            by_q: dict = {}
            for m, pr, i, q, t_enq in sorted(
                    prev, key=lambda e: (e[3], e[4][0], e[0])):
                by_q.setdefault(q, []).append((m, pr, i, t_enq))
            for q, items in by_q.items():
                cs = pl.q_cons[q]
                n_c = len(cs)
                base_c = {c: cnt_c[c] for c in cs}
                for pos, (m, pr, i, t_enq) in enumerate(items):
                    c = cs[(cnt_q[q] + pos) % n_c]
                    n_on = base_c[c] + pos // n_c
                    t[Np + m] = (np.maximum(t_enq, ack[c, n_on % P])
                                 if n_on >= P else t_enq)
                    members[Np + m] = (del_rows[(q, c)],
                                       raw[k - 1, S:2 * S, m], 1)
                    dels[m] = (pr, i, c, n_on)
                for pos in range(len(items)):
                    cnt_c[cs[(cnt_q[q] + pos) % n_c]] += 1
                cnt_q[q] += len(items)
        # the request legs' first hop, then the replies this step takes
        _serve(pl, members, t, 0, 1, free, f)
        hz_pub = (max(t[m][0] for m, *_ in pubs) - horizon if pubs else inf)
        hz_del = max(t[Np + m][0] for m in dels) if dels else inf
        idle = max([f(0.0)] + [c[0] for (r, side), cs in free.items()
                               if side == 0 and r in pl.shared for c in cs])
        go = _first({row: v[0][0] for row, v in rp_wait.items()
                     if v[0][0] <= hz_pub or idle <= v[0][0] <= hz_del}, Np)
        dep = {}
        for row, (t_q, n) in rd_wait.items():
            pr = row // M
            if n < P:
                dep[row] = t_q
            elif ptag[pr, n % P] == n - P:
                dep[row] = np.maximum(t_q, prep[pr, n % P])
        back_go = _first({row: d[0] for row, d in dep.items()
                          if d[0] <= hz_del}, Np)
        replies: dict = {}
        for j, row in enumerate(go):
            seen, c = rp_wait.pop(row)
            t[2 * Np + j] = seen
            replies[2 * Np + j] = (rp_rows[(c, row // M)],
                                   reply_jit(row, 2 * S + 1), 0)
        number = {}
        for j, row in enumerate(back_go):
            number[row] = rd_wait.pop(row)[1]
            t[3 * Np + j] = dep[row]
            replies[3 * Np + j] = (rd_rows[row // M],
                                   reply_jit(row, 3 * S + 1), 1)
        # the replies' first hop: positions in their own pass
        order = {kk: kk - 2 * Np for kk in replies}
        t_own = {order[kk]: t[kk] for kk in replies}
        _serve(pl, {order[kk]: v for kk, v in replies.items()}, t_own,
               0, 1, free, f)
        for kk in replies:
            t[kk] = t_own[order[kk]]
        members.update(replies)
        _serve(pl, members, t, 1, S, free, f)

        for m, pr, i, q in pubs:
            conf[pr, i % W] = t[m] + ctrl
        prev = [(m, pr, i, q, t[m]) for m, pr, i, q in pubs]
        if dels:
            arr = {m: t[Np + m] + recv for m in dels}
            hold = {m: proc_s * (f(1.0) + raw[k - 1, 2 * S, m])
                    for m in dels}
            by_c: dict = {}
            for m in sorted(dels, key=lambda m: (dels[m][2], arr[m][0], m)):
                by_c.setdefault(dels[m][2], []).append(m)
            for c, ms in by_c.items():
                seen = ws._fifo(ms, arr, hold, proc[c])
                proc[c] = np.maximum(proc[c], seen[ms[-1]])
                for pos, m in enumerate(ms):
                    pr, i, _c, n_on = dels[m]
                    consume[pr * M + i] = seen[m]
                    nb = min(len(ms) - 1, pos + (B - 1 - pos % B))
                    ack[c, n_on % P] = seen[ms[nb]] + ctrl
                    rp_wait[pr * M + i] = (seen[m], c)
        # each reply published takes its number in its producer's queue
        by_p: dict = {}
        for j, row in enumerate(go):
            by_p.setdefault(row // M, []).append((t[2 * Np + j][0], j, row))
        for pr, items in by_p.items():
            for pos, (_t0, j, row) in enumerate(sorted(items)):
                rd_wait[row] = (t[2 * Np + j], cnt_p[pr] + pos)
            cnt_p[pr] += len(items)
        # each reply received: its time, and the producer's batched acks
        by_p = {}
        for j, row in enumerate(back_go):
            t_seen = t[3 * Np + j] + recv_rep
            back[row] = t_seen
            by_p.setdefault(row // M, []).append((t_seen[0], j, row, t_seen))
        for pr, items in by_p.items():
            items.sort(key=lambda e: (e[0], e[1]))
            for pos, (_t0, _j, row, _ts) in enumerate(items):
                n = number[row]
                nb = min(len(items) - 1, pos + (B - 1 - pos % B))
                prep[pr, n % P] = items[nb][3] + ctrl
                ptag[pr, n % P] = n
        peak = max(peak, len(rp_wait) + len(rd_wait))
    return dict(consume=consume, publish=publish, rtt=back - publish, G=G,
                nGen=pl.nGen, peak=peak)


def throughput(consume: np.ndarray, warmup_frac: float = 0.05) -> float:
    """Aggregate consumer rate (msgs/s of simulated time) past the first
    5% of completions: the paper's throughput."""
    return ws.throughput(consume, warmup_frac)


def hops_per_message(cfg: dict, arch: str) -> float:
    """Mean resource hops on a message's publish, delivery,
    reply-publish and reply-delivery paths."""
    pl = Plan(cfg, arch)

    def mean(paths: dict) -> float:
        return sum(sum(el is not None for el in p) for p in paths.values()) \
            / len(paths)
    return mean(pl.pub) + mean(pl.dels) + mean(pl.rps) + mean(pl.rds)
