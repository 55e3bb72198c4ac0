"""Vectorized batched StreamSim engine (the default engine).

The reference engine in :mod:`repro.core.simulator` pushes one heap event
per message-hop, which is exact but caps interactive sweeps at ~10^5
messages.  This engine runs the same experiment as a *batched* discrete-
event simulation: whole message cohorts move through an architecture's hop
graph together, and every FIFO resource's busy intervals are resolved with
prefix-scan (``cumsum`` + ``maximum.accumulate``) recurrences instead of
per-message heap events.

Key ideas
---------

* **FIFO pipes as scans.**  For arrivals ``a_j`` (sorted) and hold times
  ``h_j``, the busy-interval recurrence ``e_j = max(a_j, e_{j-1}) + h_j``
  has the closed form ``e_j = H_j + max_{m<=j}(a_m - H_{m-1})`` with
  ``H = cumsum(h)`` — one ``maximum.accumulate`` per resource per cohort.
* **k-server pools as k interleaved scans.**  With near-uniform service
  times the FIFO pool recurrence ``e_j = max(a_j, e_{j-k}) + h_j`` splits
  into ``k`` independent pipe scans over strided sub-sequences.
* **Window generations.**  Publisher-confirm flow control couples message
  ``i`` to the confirm of message ``i - W``; messages are processed in
  per-producer *rounds* of ``SimParams.vec_round`` (a sub-multiple of the
  window), so every round is a feed-forward array computation.
* **Batch event loop.**  Each cohort leg (publish, delivery, reply) is an
  event keyed by its earliest arrival time at its next hop; one heap pop
  serves one hop for a whole cohort.  Cohorts therefore hit shared
  resources (NICs, tunnel, ingress, broker CPU pools) in close to true
  arrival order — the property that makes the FIFO carries honest — while
  the heap engine needs ~10^7 pops for what this loop does in ~10^3.
* **A batched broker pump.**  Queue deliveries release FIFO to the next
  consumer with an open basic.qos window (rotated round-robin, so load
  shifts toward less-congested consumers exactly when windows close, as
  in RabbitMQ); departs are gated on ack arrivals, and acks follow the
  broker's ack-multiple batching (every ``ack_batch`` deliveries, or
  immediately once the window is full).
* **Batched credit flow.**  Each queue tracks its un-drained backlog with
  an enqueue counter and a min-heap of release (depart) times.  When a
  cohort's enqueues push the backlog past the RabbitMQ credit threshold
  (``credit_flow_default_credit x publishers``, as in the heap broker),
  those members' publisher confirms are *withheld*: they resolve only
  once the batched pump has drained the queue back to half the threshold,
  at the depart time that crossed the resume mark (+ control latency).
  Withheld confirms stall the publish-round frontier exactly like the
  heap engine's channel blocking.
* **Reject-publish overflow as re-injection rounds.**  When a queue (or
  any fanout target, atomically) is at its byte cap at a member's arrival
  time, the publish is rejected and the member re-enters the publish
  path as a retry cohort after ``publish_retry_s`` — the producer
  re-publish backoff — repeating until the drain admits it.  Reply
  publishes get the same treatment on reply/gather queues.
* **Lane-resolved flow control.**  In stacked multi-seed execution every
  piece of flow-control state is per-lane: credit backlogs, depart
  cursors (one min-heap per lane, keyed by that lane's own clock),
  byte-capped admission, reject-retry cadences, deferred-confirm
  resume clocks and the rejected/blocked counters.  Scheduling stays
  the pilot's (a member joins a retry cohort iff lane 0 rejected it),
  but each lane's admission arithmetic is the exact solo sequence run
  against its own clocks — so overflow-regime cells stack, and each
  lane's counters are its own, not clones of the pilot's.
* **Utilization-triggered finer interleaving.**  A static bottleneck
  analysis of the hop graph estimates each shared DSN-side pipe's
  (``dsn_*``, ``tunnel``) utilization at the configured demand.  When one
  is saturated and few flows are in play (ordering detail then matters
  most), auto mode shrinks ``vec_round`` and ``vec_horizon_s`` so cohorts
  interleave at close to per-message granularity through the contended
  resource.  Explicit ``vec_round``/``vec_horizon_s`` settings are always
  honored.
* **Hop-graph slot alignment.**  Paths that differ only by optional
  broker-internal hops (queue homed on another node) are aligned on their
  longest common prefix/suffix of resource classes so shared bottlenecks
  are served in one merged batch per hop.

Fidelity
--------

The engine reproduces the heap engine's aggregate metrics (throughput,
median/p95 RTT, overhead ratios) to ~1% on most of the paper's operating
points, and to <=3% on the previously-documented outliers (DTS
work-sharing throughput, DTS feedback RTT, PRS gather RTT) thanks to the
utilization-triggered interleaving — see tests/test_engine_parity.py.
Credit-flow confirm withholding and reject-publish overflow (with the
producer re-publish backoff) *are* modeled, in batched form, and parity
in the overflow regime (nonzero ``rejected_publishes``, active channel
blocking) is enforced by the overflow block of the parity suite.
Message redelivery is modeled through the chaos layer
(:mod:`repro.core.chaos`): a ``SimParams.chaos`` schedule turns the run
into a sequence of topology epochs — chaos boundaries are sentinel
entries in the batch event heap (so the horizon logic never serves a
cohort across an epoch boundary), broker/queue outages are static
admission windows resolved by the same per-lane walk as byte-cap
overflow, and deliveries unacked at an outage re-enter the queue front
as redelivery segments (at-least-once duplication, zero loss) —
chaos-cell parity vs the heap engine is enforced by
tests/test_chaos.py.
"""

from __future__ import annotations

import heapq
import itertools
from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

import numpy as np

from repro.core import spans
from repro.core.architectures import (
    Architecture, PathElement, ResourceSpec, make_architecture)
from repro.core.broker import ClassicQueue
from repro.core.ds2hpc import ClusterInventory
from repro.core.simulator import (
    ENGINES, ExperimentSpec, InfeasibleConfiguration, RunResult,
    check_feasibility)

#: RabbitMQ credit_flow_default_credit, shared with the heap broker model
FLOW_CREDIT = ClassicQueue.FLOW_CREDIT

#: shrink vec_round/vec_horizon_s (auto mode) when a shared DSN-side pipe
#: is estimated at >= this fraction of the run's bottleneck...
SATURATION_UTIL = 0.85
#: ...and no more than this many concurrent flows are in play (aggregate
#: metrics stop depending on exact cross-flow ordering beyond it)
SATURATION_MAX_CLIENTS = 64

# ---------------------------------------------------------------------------
# Batched FIFO resources
# ---------------------------------------------------------------------------


def _fifo_scan(a: np.ndarray, h: np.ndarray,
               carry: float | np.ndarray) -> np.ndarray:
    """End times for FIFO service: e_j = max(a_j, e_{j-1}) + h_j, with the
    server busy until ``carry`` before the first arrival.

    Dimension-generic: arrays may carry a trailing *lane* axis (stacked
    multi-seed execution — see :meth:`VectorizedStreamSim.run_stacked`);
    the recurrence always runs along axis 0, independently per lane."""
    a = np.maximum(a, carry)
    H = np.cumsum(h, axis=0)
    return H + np.maximum.accumulate(a - (H - h), axis=0)


def _lane0(a: np.ndarray) -> np.ndarray:
    """The scheduling view of a possibly lane-stacked time array: lane 0
    (the pilot lane) drives every ordering/branching decision."""
    return a if a.ndim == 1 else a[:, 0]


class _VecResource:
    """Busy-interval state for one shared resource, served in batches.

    With ``lanes > 1`` the resource holds one carry per lane and serves
    ``(n, lanes)`` time arrays — same FIFO arithmetic per lane, with the
    pilot lane's arrival order deciding the (shared) service order."""

    __slots__ = ("spec", "_free_pipe", "_free_pool", "_scan")

    def __init__(self, spec: ResourceSpec, lanes: int = 1,
                 scan: Optional[Callable] = None) -> None:
        self.spec = spec
        #: the FIFO-scan kernel (``_fifo_scan`` or an engine-injected
        #: port of it, e.g. the JAX engine's jitted scan)
        self._scan = scan if scan is not None else _fifo_scan
        self._free_pipe = 0.0
        if spec.kind == "pool":
            k = max(1, spec.servers)
            self._free_pool = (np.zeros(k) if lanes == 1
                               else np.zeros((k, lanes)))
        else:
            self._free_pool = None

    def hold_times(self, nbytes: np.ndarray) -> np.ndarray:
        s = self.spec
        if s.kind == "pipe":
            return s.service_s + (nbytes / s.rate_Bps if s.rate_Bps else 0.0)
        return s.service_s + nbytes * s.per_byte_s

    def serve(self, t_arr: np.ndarray, nbytes: np.ndarray,
              jit: np.ndarray) -> np.ndarray:
        """FIFO-serve a batch (any order); returns per-message end times."""
        ht = self.hold_times(nbytes)
        if jit.ndim > 1 and np.ndim(ht) == 1:
            ht = ht[:, None]
        hold = ht * (1.0 + jit)
        order = np.argsort(_lane0(t_arr), kind="stable")
        a, h = t_arr[order], hold[order]
        end_sorted = np.empty_like(a)
        if self.spec.kind == "pipe":
            end_sorted = self._scan(a, h, self._free_pipe)
            self._free_pipe = (float(end_sorted[-1]) if a.ndim == 1
                               else end_sorted[-1].copy())
        else:
            # k-server pool: k interleaved chains; earliest-free server
            # takes the next arrival (exact for near-uniform hold times)
            if self._free_pool.ndim == 1:
                carry = np.sort(self._free_pool)
            else:
                carry = self._free_pool[
                    np.argsort(self._free_pool[:, 0], kind="stable")]
            k = carry.shape[0]
            n = a.shape[0]
            for c in range(min(k, n)):
                end_sorted[c::k] = self._scan(a[c::k], h[c::k], carry[c])
                carry[c] = end_sorted[c + ((n - 1 - c) // k) * k]
            self._free_pool = carry
        out = np.empty_like(end_sorted)
        out[order] = end_sorted
        return out


# ---------------------------------------------------------------------------
# Hop-graph slot alignment
# ---------------------------------------------------------------------------


def _res_class(el: Optional[PathElement]) -> Optional[str]:
    if el is None or el.resource is None:
        return None
    return el.resource.split(":", 1)[0]


def _align_paths(paths: dict) -> tuple[dict, int]:
    """Pad each path's *middle* (between the longest common prefix and
    suffix of resource classes) with Nones so shared bottlenecks land on
    the same slot across path variants.  Returns ({key: padded}, n_slots).
    """
    sigs = {k: [_res_class(e) for e in p] for k, p in paths.items()}
    sig_list = list(sigs.values())
    min_len = min(len(s) for s in sig_list)
    lcp = 0
    while lcp < min_len and len({s[lcp] for s in sig_list}) == 1:
        lcp += 1
    lcs = 0
    while (lcs < min_len - lcp
           and len({s[len(s) - 1 - lcs] for s in sig_list}) == 1):
        lcs += 1
    max_mid = max(len(s) - lcp - lcs for s in sig_list)
    out = {}
    for k, p in paths.items():
        mid = list(p[lcp:len(p) - lcs])
        out[k] = (list(p[:lcp]) + mid + [None] * (max_mid - len(mid))
                  + list(p[len(p) - lcs:]))
    return out, lcp + max_mid + lcs


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class VectorizedStreamSim:
    """Batched engine; same constructor/run contract as ``StreamSim``."""

    #: bound on the memoized (flow, combos) -> resolved-paths cache
    COMBO_CACHE_MAX = 8192

    #: the FIFO-scan kernel every busy-interval recurrence runs through
    #: (resources and the consumer processing chains); subclass engines
    #: (repro.core.jax_engine) swap in their own port
    _scan_impl = staticmethod(_fifo_scan)

    #: ``RunResult.path`` of the results this engine's event loop makes
    PATH = "vectorized"

    def __init__(self, spec: ExperimentSpec,
                 inventory: Optional[ClusterInventory] = None,
                 arch: Optional[Architecture] = None,
                 stack_seeds: Optional[list[int]] = None) -> None:
        """``stack_seeds``: run this many seed-lanes of the same cell in
        one batched event loop (cohort stacking — see
        :meth:`run_stacked`); ``None``/single-seed is the exact solo
        engine.  ``stack_seeds[0]`` becomes the *pilot* lane whose clock
        drives all scheduling decisions; its results are bit-identical
        to a solo run with that seed."""
        self.spec = spec
        self.p = spec.params
        self.inv = inventory or ClusterInventory()
        self.arch = arch or make_architecture(spec.arch, self.inv)
        self.arch.configure(spec.n_producers, spec.n_consumers,
                            tenants=spec.tenants)
        # tenant-aware hop graphs (DTS per-tenant tunnels): path combos
        # carry the client's tenant as a trailing column, so _resolve_paths
        # builds each tenant's own variant; non-tenant archs keep the
        # 3-column combos (bit-identical to the single-tenant engine)
        self._tenant_cols = bool(self.arch.tenant_paths)
        self._ppt = max(1, spec.n_producers // spec.tenants)
        self._cpt = max(1, spec.n_consumers // spec.tenants)
        check_feasibility(self.arch, spec)
        self.stack_seeds = (list(stack_seeds) if stack_seeds is not None
                            else [self.p.seed])
        self._lanes = len(self.stack_seeds)
        if self._lanes < 1:
            raise ValueError("stack_seeds must name at least one seed")
        if self.stack_seeds[0] != self.p.seed:
            raise ValueError("stack_seeds[0] (the pilot lane) must equal "
                             "params.seed")
        self._rngs = [np.random.default_rng(s) for s in self.stack_seeds]
        self.rng = self._rngs[0]
        self.resources = {k: _VecResource(s, self._lanes,
                                          scan=self._scan_impl)
                          for k, s in self.arch.resources.items()}
        self._proc_s = (self.p.consumer_proc_s
                        if self.p.consumer_proc_s is not None
                        else spec.workload.proc_time_s())
        self.n_events = 0
        #: per-lane flow-control counters (lane 0 = the pilot = the solo
        #: run's values); scalars in RunResult come from the lane's entry
        self.rejected = np.zeros(self._lanes, dtype=np.int64)
        self.blocked = np.zeros(self._lanes, dtype=np.int64)
        self._path_cache: dict = {}
        self._align_cache: dict = {}
        self._combo_cache: dict = {}
        self._channels: dict = {}
        self._queues: dict = {}
        self._chan_queue: dict = {}
        self._heap: list = []
        self._seq = itertools.count()
        #: how far past the next event's key a batch may serve ahead: 0 is
        #: strictest global time ordering (max fidelity, max fragmentation);
        #: auto mode widens with client count — the more concurrent flows,
        #: the less aggregate metrics depend on exact cross-flow ordering
        if self.p.vec_horizon_s is not None:
            self._slack = self.p.vec_horizon_s
        else:
            self._slack = max(1e-3, 1e-3 * (spec.n_producers
                                            + spec.n_consumers) / 16.0)
        # utilization-triggered finer interleaving (auto knobs only): at
        # low flow counts with a saturated shared DSN-side pipe, ordering
        # detail dominates the residual — interleave near per-message
        self._round = self.p.vec_round if self.p.vec_round is not None else 8
        self._fine_pump = False
        self.dsn_utilization, self.publish_surplus = self._cost_model()
        n_clients = spec.n_producers + spec.n_consumers
        if n_clients <= SATURATION_MAX_CLIENTS:
            if self.dsn_utilization >= SATURATION_UTIL:
                # window-aware per-message release: with few flows on a
                # saturated pipe, the adaptive consumer shift (windows on
                # congested NICs close, round-robin skips them) is a
                # first-order throughput effect the batched fast path
                # cannot reproduce
                self._fine_pump = True
                if self.p.vec_round is None:
                    self._round = 2
                if self.p.vec_horizon_s is None:
                    self._slack *= 0.25
        # chaos (topology-epoch failure injection): boundaries become
        # sentinel heap entries, outages become static admission windows,
        # redelivery re-enters pending segments (see module docstring)
        self._chaos = self.p.chaos
        self._redelivered = 0
        self._dup_times: list = []
        self._dup_mem: list = []
        self._as_extra: list = []
        self._as_next = 0
        if self._chaos is not None:
            self._chaos_check()

    # -- chaos: topology-epoch failure injection --------------------------------
    def _chaos_check(self) -> None:
        """Validate the chaos schedule against this cell (mirrors the
        heap engine's construction-time checks)."""
        spec = self.spec
        if spec.pattern != "work_sharing":
            raise ValueError("chaos schedules support pattern="
                             f"'work_sharing' only, got {spec.pattern!r}")
        if self._lanes > 1:
            raise ValueError("chaos cells do not stack; run them solo")
        for inj in self._chaos.injections:
            if inj.kind == "link":
                if not any(k == inj.target
                           or k.startswith(inj.target + ":")
                           for k in self.resources):
                    raise ValueError(
                        f"link injection target {inj.target!r} matches no "
                        f"resource of architecture {self.arch.name!r}")
            elif inj.kind == "consumer":
                if spec.tenants > 1:
                    raise ValueError(
                        "consumer injections require tenants=1")
                tgt = inj.target
                if not (tgt.startswith("c") and tgt[1:].isdigit()
                        and int(tgt[1:]) < spec.n_consumers):
                    raise ValueError(
                        f"consumer injection target {tgt!r} must name a "
                        f"configured consumer (c0..c{spec.n_consumers - 1})")
        if self._chaos.autoscale is not None and spec.tenants > 1:
            raise ValueError("autoscale policies require tenants=1")

    def _chaos_flow_possible(self) -> bool:
        """Broker outages produce flow events (publish rejects) the
        static :meth:`flow_events_possible` probe cannot see — force
        per-message publish rounds for them too."""
        return (self._chaos is not None
                and any(i.kind == "broker"
                        for i in self._chaos.injections))

    # -- work-pattern topology (shared vs per-tenant vhost queues) -------------
    def _work_topology(self) -> tuple[int, list[list[int]],
                                      list[list[int]], list[int]]:
        """Queue topology of the work-sharing/feedback patterns.

        Returns ``(nq, q_consumers, prod_queues, q_publishers)``:
        ``q_consumers[qi]`` — consumer indices attached to queue ``qi``;
        ``prod_queues[pr]`` — the queues producer ``pr`` round-robins
        over; ``q_publishers[qi]`` — how many producers publish to
        ``qi`` (its credit-flow threshold multiplier).  Queue indices
        follow the heap engine's declare order, so home nodes line up.
        With ``tenants > 1`` and vhost isolation, tenant ``t`` owns
        queues ``[t*nq_t, (t+1)*nq_t)`` and only its own producers/
        consumers touch them."""
        spec, p = self.spec, self.p
        nP, nC = spec.n_producers, spec.n_consumers
        if spec.tenants > 1 and spec.tenant_isolation == "vhost":
            T = spec.tenants
            ppt, cpt = nP // T, nC // T
            nq_t = min(p.n_work_queues, cpt)
            nq = T * nq_t
            q_consumers = [
                t * cpt + np.flatnonzero(np.arange(cpt) % nq_t == qi)
                for t in range(T) for qi in range(nq_t)]
            prod_queues = [
                [(pr // ppt) * nq_t + qi for qi in range(nq_t)]
                for pr in range(nP)]
            q_publishers = [ppt] * nq
        else:
            nq = min(p.n_work_queues, nC)
            q_consumers = [np.flatnonzero(np.arange(nC) % nq == qi)
                           for qi in range(nq)]
            prod_queues = [list(range(nq))] * nP
            q_publishers = [nP] * nq
        return nq, q_consumers, prod_queues, q_publishers

    def flow_events_possible(self) -> bool:
        """Static reachability test for broker flow-control events
        (credit-flow confirm withholding / reject-publish overflow):
        True when producers can pile a queue's backlog past its credit
        threshold, or a byte cap sits below the per-queue volume.  Used
        by the auto ``vec_round`` heuristic (drop to per-message rounds
        at the blocking boundary).  Since flow control became
        lane-resolved, :func:`run_many` stacks these cells like any
        other — this probe no longer gates stacking."""
        spec, p = self.spec, self.p
        size = spec.workload.payload_bytes
        cap = (p.queue_max_bytes // size) if p.queue_max_bytes else None
        per_producer = spec.total_messages // max(1, spec.n_producers)
        if spec.pattern in ("work_sharing", "feedback"):
            nq, _, _, q_pubs = self._work_topology()
            per_q = per_producer * spec.n_producers / nq
            credit = FLOW_CREDIT * min(q_pubs)
        else:
            per_q = per_producer
            credit = FLOW_CREDIT
        return ((cap is not None and cap < per_q)
                or credit < self.publish_surplus * per_q)

    # -- static bottleneck analysis --------------------------------------------
    def _cost_model(self) -> tuple[float, float]:
        """Returns ``(dsn_utilization, publish_surplus)``.

        Accumulates, per resource, the busy seconds one *system message*
        (one consumed copy) induces, using the same node/queue placement
        as the run methods.  The resource with the largest per-message
        busy time is the bottleneck.

        ``dsn_utilization`` — the busiest shared DSN-side pipe
        (``dsn_*``/``tunnel``) as a fraction of the bottleneck; a pipe
        near 1.0 serves back-to-back, where batch-order detail matters
        most.

        ``publish_surplus`` — ``1 - (publish-leg bottleneck / overall
        bottleneck)``: the fraction of published messages that pile up as
        queue backlog because producers outpace the drain.  Scaled by the
        per-queue message volume this bounds the reachable backlog, which
        decides whether credit-flow blocking / overflow can fire."""
        spec, p, inv = self.spec, self.p, self.inv
        nP, nC = spec.n_producers, spec.n_consumers
        size = spec.workload.payload_bytes
        rsize = max(1, int(size * p.reply_factor))
        legs: list[tuple[str, tuple, float, int]] = []
        pat = spec.pattern
        # tenant-aware hop graphs: the path-constructor combos carry the
        # client's tenant as a trailing argument (same convention as the
        # run methods' combo columns)
        tcols = self._tenant_cols
        p_t = (lambda pr: ((pr // self._ppt,) if tcols else ()))
        c_t = (lambda c: ((c // self._cpt,) if tcols else ()))
        if pat in ("work_sharing", "feedback"):
            nq, q_consumers, prod_queues, _ = self._work_topology()
            q_home = [q % inv.n_dsn for q in range(nq)]
            reply_home = [(nq + pr) % inv.n_dsn for pr in range(nP)]
            for pr in range(nP):
                for qi in prod_queues[pr]:
                    legs.append(("publish_path",
                                 (pr % inv.n_producer_nodes, pr % inv.n_dsn,
                                  q_home[qi]) + p_t(pr),
                                 1.0 / (nP * len(prod_queues[pr])), size))
            for qi in range(nq):
                members = q_consumers[qi]
                for c in members:
                    legs.append(("delivery_path",
                                 ((int(c) + 1) % inv.n_dsn, q_home[qi],
                                  int(c) % inv.n_consumer_nodes)
                                 + c_t(int(c)),
                                 1.0 / (nq * len(members)), size))
            if pat == "feedback":
                # collapse the (consumer x producer) cross product over
                # the <= n_dsn distinct reply homes, tenant by tenant (a
                # vhosted consumer replies only to its own producers)
                T = (spec.tenants if spec.tenant_isolation == "vhost"
                     else 1)
                ppt, cpt = nP // T, nC // T
                for t in range(T):
                    home_w: dict[int, float] = {}
                    for pr in range(t * ppt, (t + 1) * ppt):
                        h = reply_home[pr]
                        home_w[h] = home_w.get(h, 0.0) + 1.0 / ppt
                    for c in range(t * cpt, (t + 1) * cpt):
                        for h, w in home_w.items():
                            legs.append(("reply_publish_path",
                                         (c % inv.n_consumer_nodes,
                                          (c + 1) % inv.n_dsn, h)
                                         + c_t(c),
                                         w / nC, rsize))
                for pr in range(nP):
                    legs.append(("reply_delivery_path",
                                 (reply_home[pr], pr % inv.n_dsn,
                                  pr % inv.n_producer_nodes) + p_t(pr),
                                 1.0 / nP, rsize))
        else:
            gather_home = nC % inv.n_dsn
            legs.append(("publish_path", (0, 0, 0), 1.0 / nC, size))
            for c in range(nC):
                legs.append(("delivery_path",
                             ((c + 1) % inv.n_dsn, c % inv.n_dsn,
                              c % inv.n_consumer_nodes), 1.0 / nC, size))
            if pat == "broadcast_gather":
                for c in range(nC):
                    legs.append(("reply_publish_path",
                                 (c % inv.n_consumer_nodes,
                                  (c + 1) % inv.n_dsn, gather_home),
                                 1.0 / nC, rsize))
                legs.append(("reply_delivery_path", (gather_home, 0, 0),
                             1.0, rsize))
        cost: dict[str, float] = {}
        pub_cost: dict[str, float] = {}
        for flow, combo, w, sz in legs:
            for el in getattr(self.arch, flow)(*combo):
                if el.resource is None:
                    continue
                rs = self.resources[el.resource].spec
                nb = sz * el.byte_factor + el.extra_bytes
                if rs.kind == "pipe":
                    sec = rs.service_s + (nb / rs.rate_Bps
                                          if rs.rate_Bps else 0.0)
                else:
                    sec = ((rs.service_s + nb * rs.per_byte_s)
                           / max(1, rs.servers))
                cost[el.resource] = cost.get(el.resource, 0.0) + w * sec
                if flow == "publish_path":
                    pub_cost[el.resource] = (pub_cost.get(el.resource, 0.0)
                                             + w * sec)
        c_max = max(max(cost.values(), default=0.0),
                    self._proc_s / max(1, nC))
        #: per-resource busy seconds per system message + the bottleneck,
        #: kept for external probes (patterns.deployment_feasibility reads
        #: the shared facility-ingress utilization off a built engine)
        self.resource_cost = dict(cost)
        self.bottleneck_cost = c_max
        if c_max <= 0.0:
            return 0.0, 0.0
        shared = [v for k, v in cost.items()
                  if k.startswith(("dsn_in", "dsn_out", "dsn_int", "tunnel",
                                   "dts_gw", "ttun"))]
        pub_max = max(pub_cost.values(), default=0.0)
        return (max(shared, default=0.0) / c_max,
                max(0.0, 1.0 - pub_max / c_max))

    # -- helpers ---------------------------------------------------------------
    def _jit(self, n: int) -> np.ndarray:
        """Service-time jitter draws: ``(n,)`` solo, ``(n, lanes)`` when
        stacked — each lane consumes its own generator in the (shared)
        event order, so the pilot lane's stream matches a solo run."""
        j = self.p.jitter
        if self._lanes == 1:
            return self.rng.uniform(-j, j, n) if j else np.zeros(n)
        if not j:
            return np.zeros((n, self._lanes))
        out = np.empty((n, self._lanes))
        for lane, g in enumerate(self._rngs):
            out[:, lane] = g.uniform(-j, j, n)
        return out

    def _recv_latency(self, size: int) -> float:
        return self.arch.recv_latency_s(size)

    def _chan(self, cid: int) -> dict:
        """Broker-channel state: per-delivery seen/ack times (the ack
        clock), the ack-multiple coverage cursor, and the consumer's
        serial-processing carry.  The clock arrays carry a trailing lane
        axis in stacked mode."""
        ch = self._channels.get(cid)
        if ch is None:
            shape = (0,) if self._lanes == 1 else (0, self._lanes)
            ch = {"assigned": 0, "acked": 0, "seen": np.zeros(shape),
                  "ack_time": np.zeros(shape), "free": 0.0,
                  "since": 0, "last_tag": 0}
            self._channels[cid] = ch
        return ch

    @staticmethod
    def _chan_grow(ch: dict, extra: int) -> None:
        """Amortized growth of the per-delivery bookkeeping arrays."""
        need = ch["assigned"] + extra
        if ch["seen"].shape[0] < need:
            cap = max(need, 2 * ch["seen"].shape[0], 64)
            for f in ("seen", "ack_time"):
                a = np.full((cap,) + ch[f].shape[1:], np.nan)
                a[:ch[f].shape[0]] = ch[f]
                ch[f] = a

    def _resolve_paths(self, flow: str, combos: np.ndarray) -> tuple:
        """Per-combo aligned paths + member indices for one cohort leg.

        The full resolution is a pure function of ``(flow, combos)``, so
        repeated cohort shapes (the same consumer rotation recurring
        across pump chunks, or the same cohort in another stacked lane)
        hit ``_combo_cache`` and skip the row-dedup entirely."""
        ckey = (flow, combos.shape[0], combos.tobytes())
        hit = self._combo_cache.get(ckey)
        if hit is not None:
            return hit
        ctor = getattr(self.arch, flow)
        uniq, inv = np.unique(combos, axis=0, return_inverse=True)
        inv = inv.ravel()
        raw = {}
        for u, key in enumerate(map(tuple, uniq)):
            ck = (flow, key)
            if ck not in self._path_cache:
                self._path_cache[ck] = ctor(*key)
            raw[u] = self._path_cache[ck]
        ak = (flow, tuple(map(tuple, uniq)))
        if ak not in self._align_cache:
            self._align_cache[ak] = _align_paths(raw)
        aligned, n_slots = self._align_cache[ak]
        idx_by = {u: np.nonzero(inv == u)[0] for u in aligned}
        if len(self._combo_cache) >= self.COMBO_CACHE_MAX:
            self._combo_cache.clear()     # crude but bounded
        self._combo_cache[ckey] = (aligned, idx_by, n_slots)
        return aligned, idx_by, n_slots

    # -- queue backlog accounting (credit flow + overflow) ---------------------
    def _queue_state(self, qkey: tuple, consumers: list[int],
                     size: int, *,
                     credit: Optional[int] = None,
                     cap_msgs: Optional[int] = None) -> dict:
        """Get/create one broker queue's batched state.

        Beyond the pump state (consumers + pending segments), queues whose
        publishers are subject to credit flow or whose byte budget can
        overflow track their un-drained backlog **per lane**: ``n_enq[l]``
        counts lane ``l``'s enqueues, released depart times sit in one
        min-heap per lane (keyed by that lane's own clock) and are popped
        (in time order) into ``departed[l]`` as the backlog is queried —
        so ``n_enq[l] - departed[l]`` is lane ``l``'s ready count at the
        query time, exactly the heap broker's ``len(q.ready)`` in that
        lane's solo run.  ``hwm[l]`` records the admission path's
        backlog high-water mark (exact in the slow path, the zero-drain
        upper bound in the fast path) — the invariant ``hwm <= cap`` is
        property-tested.  ``released`` counts recorded depart *entries*
        (each entry carries every lane), shared across lanes."""
        q = self._queues.get(qkey)
        if q is None:
            L = self._lanes
            q = {"consumers": [int(c) for c in consumers], "pending": [],
                 "size": size, "credit": credit, "cap": cap_msgs,
                 "track": credit is not None or cap_msgs is not None,
                 "n_enq": np.zeros(L, dtype=np.int64), "released": 0,
                 "departed": np.zeros(L, dtype=np.int64),
                 "depart_heap": [[] for _ in range(L)],
                 "last_pop_t": np.zeros(L), "deferred": [],
                 "hwm": np.zeros(L, dtype=np.int64),
                 "forced": np.zeros(L, dtype=np.int64)}
            self._queues[qkey] = q
            for c in q["consumers"]:
                self._chan_queue[c] = qkey
        return q

    def _pop_lane(self, q: dict, lane: int, t: float) -> None:
        """Advance one lane's depart cursor: count that lane's releases
        that left by ``t`` (the lane's own clock)."""
        h = q["depart_heap"][lane]
        while h and h[0] <= t:
            q["last_pop_t"][lane] = heapq.heappop(h)
            q["departed"][lane] += 1

    def _next_drain(self, q: dict, lane: int) -> Optional[float]:
        """Earliest recorded, not-yet-popped depart time on one lane
        (``None`` when the lane has no known future drain).  The
        depart-store read the admission retry logic keys on — engines
        with a different store (the JAX engine's masked arrays) override
        this and the pop methods, nothing else."""
        h = q["depart_heap"][lane]
        return h[0] if h else None

    def _pop_to_target(self, q: dict, lane: int, target: int) -> None:
        """Advance one lane's depart cursor until ``target`` total
        releases have been popped (best effort — stops when no recorded
        drain remains)."""
        h = q["depart_heap"][lane]
        while q["departed"][lane] < target and h:
            q["last_pop_t"][lane] = heapq.heappop(h)
            q["departed"][lane] += 1

    def _record_departs(self, q: dict, departs: np.ndarray) -> None:
        """Register released deliveries' depart times (each lane's column
        into that lane's heap); resolves any credit-flow-deferred
        confirms the new drains now admit."""
        if not q["track"]:
            return
        heaps = q["depart_heap"]
        cols = departs.reshape(departs.shape[0], self._lanes)
        for lane in range(self._lanes):
            h = heaps[lane]
            for d in cols[:, lane]:
                heapq.heappush(h, float(d))
        q["released"] += departs.shape[0]
        if q["deferred"]:
            self._try_resume(q)

    def _lane_resume_time(self, q: dict, lane: int) -> float:
        """One lane's ``flow_resume`` clock: pop that lane's departs
        until it has drained to half the credit threshold (best effort —
        with no further known drains the last release stands) and return
        the crossing depart time + control latency."""
        target = q["n_enq"][lane] - q["credit"] // 2
        self._pop_to_target(q, lane, target)
        return float(q["last_pop_t"][lane]) + self.arch.control_latency_s()

    def _try_resume(self, q: dict, force: bool = False) -> bool:
        """Release the queue's withheld confirms once drained to half the
        credit threshold (the heap broker's ``flow_resume``), at the
        depart time that crossed the mark + control latency.

        Scheduling is the pilot's: the gate and the resume clock passed
        to the resolvers are lane 0's; a resolver for a multi-lane
        deferral computes the other blocked lanes' resume clocks from
        their own depart heaps (:meth:`_lane_resume_time`) when it
        fires."""
        if not q["deferred"]:
            return False
        target = int(q["n_enq"][0]) - q["credit"] // 2
        if q["released"] < target and not force:
            return False
        self._pop_to_target(q, 0, target)
        t_resume = float(q["last_pop_t"][0]) + self.arch.control_latency_s()
        resolvers, q["deferred"] = q["deferred"], []
        for fn in resolvers:
            fn(t_resume)
        return True

    def _lane_admit(self, tracked: list, lane: int, t_rej: float
                    ) -> tuple[float, int, Optional[dict]]:
        """Resolve one non-pilot lane's reject-retry loop locally: the
        lane's producer re-publishes every ``publish_retry_s`` until the
        lane's own backlog admits the message (checked against the
        lane's depart heap — the drains this lane has already computed).
        The re-publish transits themselves are not re-served through the
        lane's resources (the member's schedule is the pilot's); the
        admission *time* and the per-attempt reject counts are the
        lane's own.  Called after the lane's attempt at ``t_rej`` was
        already rejected (and counted).  Returns ``(t_admit,
        extra_rejects, blocked_on)``; with no further known drains the
        next attempt is admitted optimistically."""
        p = self.p
        t = t_rej + p.publish_retry_s
        extra = 0
        while True:
            full_q = None
            outage_end = None
            for q in tracked:
                oe = self._outage_end(q, t)
                if oe is not None:
                    # paused queue: every retry until the outage ends is
                    # rejected — jump the cadence straight past it
                    full_q, outage_end = q, oe
                    break
                self._pop_lane(q, lane, t)
                if (q["cap"] is not None
                        and q["n_enq"][lane] - q["departed"][lane]
                        >= q["cap"]):
                    full_q = q
                    break
            if full_q is None:
                break
            nd = (outage_end if outage_end is not None
                  else self._next_drain(full_q, lane))
            if nd is None:
                # no known future drain: count this failed attempt and
                # admit on the next one rather than spinning forever —
                # the one admission that may push a lane's backlog past
                # the cap, recorded in ``forced`` (the property suite
                # bounds hwm by cap + forced)
                extra += 1
                t += p.publish_retry_s
                for q in tracked:
                    q["forced"][lane] += 1
                break
            # every retry until the next known drain fails too: jump the
            # retry cadence straight past it
            k = max(1, int(np.ceil((nd - t) / p.publish_retry_s)))
            extra += k
            t += k * p.publish_retry_s
        blocked_on = None
        for q in tracked:
            q["n_enq"][lane] += 1
            q["hwm"][lane] = max(q["hwm"][lane],
                                 q["n_enq"][lane] - q["departed"][lane])
        for q in tracked:
            if (q["credit"] is not None
                    and q["n_enq"][lane] - q["departed"][lane]
                    > q["credit"]):
                blocked_on = q
                break
        return t, extra, blocked_on

    def _enqueue_batch(self, qs: list, t_enq: np.ndarray,
                       skip: Optional[np.ndarray] = None
                       ) -> tuple[np.ndarray, Optional[np.ndarray]]:
        """Admit a publish cohort onto one queue (or atomically onto all
        fanout targets), independently per lane.  ``t_enq`` is ``(n,)``
        solo or ``(n, lanes)``; ``skip[k, l]`` marks members a previous
        attempt already admitted in lane ``l`` (they are neither
        re-enqueued nor re-counted).  Returns ``(accepted, blocked_on)``:
        ``accepted[k, l]`` — admitted in lane ``l`` by *this* attempt;
        ``blocked_on`` — ``None`` when no lane crossed a credit
        threshold, else an ``(n, lanes)`` object array whose entries name
        the queue whose threshold that member crossed in that lane.

        Each lane runs the exact solo admission sequence against its own
        clocks and depart cursor: a fast path when even a zero-drain
        upper bound on every target's backlog stays below both the byte
        cap and the credit threshold, else the per-message arrival-order
        walk (the heap engine's ``offer()``/``flow_blocked`` sequence).
        Lanes choose fast/slow independently, so a lane near its
        threshold never drags the others onto the slow path (and the
        pilot's arithmetic stays bit-identical to a solo run)."""
        L = self._lanes
        n = t_enq.shape[0]
        T = t_enq.reshape(n, L)
        tracked = [q for q in qs if q["track"]]
        if not tracked:
            acc = np.ones((n, L), dtype=bool)
            if skip is not None:
                acc &= ~skip
            return acc, None
        accept = np.zeros((n, L), dtype=bool)
        blocked_on: Optional[np.ndarray] = None
        for lane in range(L):
            att = (np.ones(n, dtype=bool) if skip is None
                   else ~skip[:, lane])
            n_att = int(att.sum())
            if n_att == 0:
                continue
            tl = T[att, lane]
            t_min = float(tl.min())
            fast = True
            for q in tracked:
                self._pop_lane(q, lane, t_min)
                hi = q["n_enq"][lane] + n_att - q["departed"][lane]
                if ((q["cap"] is not None and hi > q["cap"])
                        or (q["credit"] is not None and hi > q["credit"])
                        or self._outages_overlap(q, t_min,
                                                 float(tl.max()))):
                    fast = False
                    break
            if fast:
                for q in tracked:
                    q["n_enq"][lane] += n_att
                    q["hwm"][lane] = max(
                        q["hwm"][lane],
                        q["n_enq"][lane] - q["departed"][lane])
                accept[att, lane] = True
                continue
            ks = np.nonzero(att)[0][np.argsort(tl, kind="stable")]
            admitted, blocked = self._admit_walk(tracked, lane, ks, T)
            accept[admitted, lane] = True
            for k, q in blocked:
                if blocked_on is None:
                    blocked_on = np.full((n, L), None, dtype=object)
                blocked_on[k, lane] = q
        return accept, blocked_on

    def _admit_walk(self, tracked: list, lane: int, ks: np.ndarray,
                    T: np.ndarray) -> tuple[np.ndarray, list]:
        """One lane's per-message arrival-order admission walk (the heap
        engine's ``offer()``/``flow_blocked`` sequence): members ``ks``
        — already sorted by this lane's arrival time — are admitted
        unless a tracked queue's backlog sits at its byte cap at the
        member's arrival clock; each admission bumps every target's
        enqueue count and high-water mark, and the first credit
        threshold it crosses is recorded.  Returns ``(admitted_members,
        [(member, blocking_queue), ...])``.  The JAX engine overrides
        this with a ``lax.scan`` over the same recurrence."""
        admitted = []
        blocked = []
        for k in ks:
            t = float(T[k, lane])
            full = False
            for q in tracked:
                # a paused queue rejects publishes like a full one (the
                # heap broker's offer() during a chaos outage)
                if self._outage_end(q, t) is not None:
                    full = True
                    break
                self._pop_lane(q, lane, t)
                if (q["cap"] is not None
                        and q["n_enq"][lane] - q["departed"][lane]
                        >= q["cap"]):
                    full = True
                    break
            if full:
                continue
            admitted.append(int(k))
            for q in tracked:
                q["n_enq"][lane] += 1
                q["hwm"][lane] = max(
                    q["hwm"][lane],
                    q["n_enq"][lane] - q["departed"][lane])
            for q in tracked:
                if (q["credit"] is not None
                        and q["n_enq"][lane] - q["departed"][lane]
                        > q["credit"]):
                    blocked.append((int(k), q))
                    break
        return np.asarray(admitted, dtype=int), blocked

    # -- batch event loop ------------------------------------------------------
    def _push_transit(self, t0: np.ndarray, size: int, flow: str,
                      combos: np.ndarray,
                      on_done: Optional[Callable[[np.ndarray], None]] = None,
                      on_part: Optional[Callable[[np.ndarray, np.ndarray],
                                                 None]] = None) -> None:
        """Queue a cohort to traverse ``flow``'s hop graph, one hop per
        event pop, interleaved with every other in-flight cohort.

        ``on_done(times)`` fires once, when every member has exited;
        ``on_part(member_indices, times)`` instead fires per finishing
        sub-batch, in event order — use it when downstream state (ack
        clocks) must advance as individual messages land."""
        aligned, idx_by, n_slots = self._resolve_paths(flow, combos)
        t0 = np.asarray(t0, dtype=float)
        n = t0.shape[0]
        inv = np.empty(n, dtype=int)
        for u, idx in idx_by.items():
            inv[idx] = u
        cohort = {"out": np.empty(t0.shape), "remaining": n,
                  "on_done": on_done, "on_part": on_part,
                  "aligned": aligned, "size": size, "flow": flow}
        batch = {"t": t0.copy(), "members": np.arange(n), "inv": inv,
                 "slot": 0, "n_slots": n_slots, "cohort": cohort}
        self._push(batch)

    def _push(self, batch: dict) -> None:
        heapq.heappush(self._heap,
                       (float(_lane0(batch["t"]).min()),
                        next(self._seq), batch))

    def _split_horizon(self, batch: dict) -> dict:
        """Split off the members past the event horizon (next event's key
        + slack) back into the heap; returns the head sub-batch.  This is
        what keeps every resource seeing its customers in near-global
        arrival order even when cohort spans overlap."""
        if self._heap:
            horizon = self._heap[0][0] + self._slack
            head = _lane0(batch["t"]) <= horizon
            if not head.all():
                if not head.any():
                    head[np.argmin(_lane0(batch["t"]))] = True
                tail = {"t": batch["t"][~head],
                        "members": batch["members"][~head],
                        "inv": batch["inv"][~head],
                        "slot": batch["slot"],
                        "n_slots": batch["n_slots"],
                        "cohort": batch["cohort"]}
                self._push(tail)
                batch = {"t": batch["t"][head],
                         "members": batch["members"][head],
                         "inv": batch["inv"][head],
                         "slot": batch["slot"],
                         "n_slots": batch["n_slots"],
                         "cohort": batch["cohort"]}
        return batch

    def _prepare_slot(self, batch: dict) -> list:
        """Resolve one hop for a cohort batch into servable parts.

        Applies latency-only elements in place and returns
        ``[(resource_key, idx, nbytes, latency, jitter), ...]`` — members
        at the same hop hitting the same resource instance (across path
        variants) merged into one FIFO part, with the per-part jitter
        already drawn (in deterministic part order, so a stacked multi-
        lane run consumes each lane's RNG exactly like a solo run)."""
        cohort = batch["cohort"]
        t, s = batch["t"], batch["slot"]
        aligned = cohort["aligned"]
        size = cohort["size"]
        inv = batch["inv"]
        if len(aligned) == 1:
            groups = [(0, np.arange(t.shape[0]))]
        else:
            order = np.argsort(inv, kind="stable")
            uniq, starts = np.unique(inv[order], return_index=True)
            bounds = np.append(starts, inv.size)
            groups = [(u, order[bounds[i]:bounds[i + 1]])
                      for i, u in enumerate(uniq)]
        by_instance: dict[str, list] = {}
        for u, idx in groups:
            el = aligned[u][s]
            if el is None:
                continue
            if el.resource is None:
                t[idx] += el.latency_s
                continue
            by_instance.setdefault(el.resource, []).append((idx, el))
        parts = []
        for key, ps in by_instance.items():
            if len(ps) == 1:
                idx, el = ps[0]
                nbytes = size * el.byte_factor + el.extra_bytes
                lat = el.latency_s
            else:
                idx = np.concatenate([p[0] for p in ps])
                nbytes = np.concatenate([
                    np.full(p[0].size, size * p[1].byte_factor
                            + p[1].extra_bytes) for p in ps])
                lat = np.concatenate([
                    np.full(p[0].size, p[1].latency_s) for p in ps])
                if self._lanes > 1:
                    lat = lat[:, None]
            parts.append((key, idx, nbytes, lat, self._jit(idx.size)))
        return parts

    def _finish_slot(self, batch: dict) -> None:
        """Advance a served batch: requeue the next hop, or complete the
        cohort (fire ``on_part``/``on_done``)."""
        cohort = batch["cohort"]
        t = batch["t"]
        batch["slot"] += 1
        if batch["slot"] < batch["n_slots"]:
            self._push(batch)
        else:
            if cohort["on_part"] is not None:
                cohort["on_part"](batch["members"], t)
            cohort["out"][batch["members"]] = t
            cohort["remaining"] -= t.shape[0]
            if cohort["remaining"] == 0 and cohort["on_done"] is not None:
                cohort["on_done"](cohort["out"])

    def _serve_slot(self, batch: dict) -> None:
        """Serve one hop for the head of one cohort batch."""
        batch = self._split_horizon(batch)
        for key, idx, nbytes, lat, jit in self._prepare_slot(batch):
            batch["t"][idx] = (self.resources[key].serve(
                batch["t"][idx], nbytes, jit) + lat)
            self.n_events += idx.size
        self._finish_slot(batch)

    def _pop_batch(self) -> Optional[dict]:
        """Pop the next cohort batch, honoring the same safety caps the
        heap engine enforces; None when drained (or capped out)."""
        if not self._heap:
            return None
        key, _, batch = heapq.heappop(self._heap)
        if (self.n_events > self.p.max_events
                or key > self.p.max_sim_time):
            self._heap.clear()
            return None
        return batch

    def _drain(self) -> None:
        while True:
            batch = self._pop_batch()
            if batch is None:
                break
            fn = batch.get("chaos_fn")
            if fn is not None:
                # epoch boundary: mutate topology state between batches
                # (the horizon in _split_horizon keeps cohorts from
                # being served past this key before it pops)
                fn(batch["t_evt"])
                continue
            self._serve_slot(batch)

    def _force_resume(self) -> bool:
        """Last-resort deadlock breaker for the drained-out tail: resolve
        any still-deferred confirms at the release clock."""
        any_resolved = False
        for q in self._queues.values():
            if q["deferred"] and self._try_resume(q, force=True):
                any_resolved = True
        return any_resolved

    def _tail_step(self) -> bool:
        """One end-of-drain recovery step, called with the heap empty:
        force-flush unflushed batch acks that hold back window-waiting
        deliveries (the heap engine's expected-consumed flush), then
        force-resume deferred confirms.  True when new events appeared."""
        flushed = []
        for c, ch in self._channels.items():
            if ch["last_tag"] > ch["acked"]:
                j = np.arange(ch["acked"], ch["last_tag"])
                if not np.isfinite(ch["seen"][j]).all():
                    continue
                ch["ack_time"][j] = (ch["seen"][j]
                                     + self.arch.control_latency_s())
                ch["acked"] = ch["last_tag"]
                ch["since"] = 0
                if c in self._chan_queue:
                    flushed.append(self._chan_queue[c])
        if flushed:
            self._pump_queues(flushed)
            if self._heap:
                return True
        if self._force_resume() and self._heap:
            return True
        return False

    def _drain_all(self) -> None:
        """Drain the event heap; when only unflushed batch acks hold back
        window-waiting deliveries (the tail of a run), force-flush them —
        the heap engine's expected-consumed flush — and keep draining."""
        while True:
            self._drain()
            if not self._tail_step():
                return

    # -- chaos runtime (epoch boundaries + outage admission + redelivery) ------
    def _push_chaos(self, t: float, fn: Callable[[float], None]) -> None:
        """Schedule one epoch boundary as a sentinel heap entry."""
        heapq.heappush(self._heap, (float(t), next(self._seq),
                                    {"chaos_fn": fn, "t_evt": float(t)}))

    def _chaos_setup(self, nq: int, q_home: np.ndarray) -> None:
        """Resolve injection targets against the built work topology and
        schedule every epoch boundary (called from ``_setup_work``)."""
        sched, spec = self._chaos, self.spec
        T = spec.tenants if (spec.tenants > 1
                             and spec.tenant_isolation == "vhost") else 1
        nq_t = nq // T
        for inj in sched.injections:
            if inj.kind == "link":
                keys = [k for k in self.resources
                        if k == inj.target or k.startswith(inj.target + ":")]
                self._push_chaos(inj.t0,
                                 lambda t, ks=keys, t1=inj.t1:
                                 self._chaos_link_down(ks, t1))
            elif inj.kind == "broker":
                qis = self._chaos_queue_indices(inj.target, nq, nq_t,
                                                q_home, self.inv.n_dsn)
                for qi in qis:
                    q = self._queues[("work", qi)]
                    q.setdefault("outages", []).append((inj.t0, inj.t1))
                    q.setdefault("log", [])
                self._push_chaos(inj.t0,
                                 lambda t, qs=tuple(qis), t1=inj.t1:
                                 self._chaos_queues_down(qs, t, t1))
                self._push_chaos(inj.t1,
                                 lambda t, qs=tuple(qis):
                                 self._pump_queues([("work", qi)
                                                    for qi in qs]))
            else:                       # consumer
                cidx = int(inj.target[1:])
                self._queues[("work", cidx % nq)].setdefault("log", [])
                self._push_chaos(inj.t0,
                                 lambda t, c=cidx:
                                 self._chaos_consumer_down(c, t))
                self._push_chaos(inj.t1,
                                 lambda t, c=cidx:
                                 self._chaos_consumer_up(c))
        if sched.autoscale is not None:
            self._as_next = spec.n_consumers
            self._chaos_work_qkeys = [("work", qi) for qi in range(nq)]
            self._push_chaos(sched.autoscale.interval_s,
                             self._chaos_autoscale_tick)

    @staticmethod
    def _chaos_queue_indices(target: str, nq: int, nq_t: int,
                             q_home: np.ndarray, n_dsn: int) -> list[int]:
        """Map a broker-injection target to work-queue indices, following
        the heap engine's declare order/grammar: ``queue:<name>`` (name
        as the heap broker declares it, e.g. ``work:0`` or
        ``t1/work:0``), ``node:<k>`` (queues homed on DSN node ``k``) or
        ``vhost:t<t>`` (a tenant's queue block)."""
        kind, _, val = target.partition(":")
        if kind == "queue":
            try:
                if "/" in val:
                    vh, base = val.split("/", 1)
                    qis = [int(vh[1:]) * nq_t
                           + int(base.rsplit(":", 1)[1])]
                else:
                    qis = [int(val.rsplit(":", 1)[1])]
            except (IndexError, ValueError):
                raise ValueError(f"unknown queue in broker injection "
                                 f"target {target!r}") from None
            if not all(0 <= qi < nq for qi in qis):
                raise ValueError(f"unknown queue in broker injection "
                                 f"target {target!r}")
        elif kind == "node":
            node = int(val)
            if not 0 <= node < n_dsn:
                raise ValueError(
                    f"broker injection target {target!r} names no DSN "
                    f"node (cluster has {n_dsn})")
            # empty match allowed: a node homing no work queue is a
            # no-op fault (the redundancy the availability sweep probes)
            return [qi for qi in range(nq) if int(q_home[qi]) == node]
        elif kind == "vhost":
            t = int(val[1:])
            qis = list(range(t * nq_t, (t + 1) * nq_t))
        else:
            raise ValueError(f"broker injection target {target!r} must be "
                             "'queue:<name>', 'node:<k>' or 'vhost:<v>'")
        if not qis:
            raise ValueError(f"broker injection target {target!r} matches "
                             "no queue")
        return qis

    def _chaos_link_down(self, keys: list[str], t1: float) -> None:
        """Link outage at its t0 boundary: the matched resources accept
        no new service until ``t1`` (started holds complete — exactly
        the heap engine's carry bump)."""
        for k in keys:
            res = self.resources[k]
            res._free_pipe = np.maximum(res._free_pipe, t1)
            if res._free_pool is not None:
                res._free_pool = np.maximum(res._free_pool, t1)

    @staticmethod
    def _outage_end(q: dict, t: float) -> Optional[float]:
        """End of the outage window covering time ``t`` (None if up)."""
        for (o0, o1) in q.get("outages") or ():
            if o0 <= t < o1:
                return o1
        return None

    @staticmethod
    def _outages_overlap(q: dict, lo: float, hi: float) -> bool:
        """Any outage window intersecting arrival span ``[lo, hi]``."""
        return any(o0 <= hi and o1 > lo
                   for (o0, o1) in q.get("outages") or ())

    def _chaos_clamp(self, q: dict, depart: np.ndarray) -> np.ndarray:
        """A paused queue delivers nothing: departs landing inside an
        outage window slide to its end (the heap broker's unpause pump).
        """
        for (o0, o1) in q.get("outages") or ():
            depart = np.where((depart >= o0) & (depart < o1), o1, depart)
        return depart

    def _chaos_queues_down(self, qis: tuple, t0: float, t1: float) -> None:
        """Broker/vhost outage at its t0 boundary: requeue broker-unacked
        deliveries to the front of each paused queue, ready at ``t1``."""
        touched = []
        for qi in qis:
            qk = ("work", qi)
            if self._chaos_requeue(qk, t0, t1):
                touched.append(qk)
        if touched:
            self._pump_queues(touched)

    def _chaos_requeue(self, qkey: tuple, t_cut: float, t_ready: float,
                       cons_filter: Optional[int] = None) -> int:
        """Re-enter every delivery that departed by ``t_cut`` but was
        still unacked at the broker then (ack arrival after ``t_cut``)
        as a front pending segment ready at ``t_ready`` — at-least-once
        redelivery: the original completion stands, the redelivered copy
        records a duplicate consume time.  Returns the redelivery count.
        """
        q = self._queues[qkey]
        log = q.get("log")
        if not log:
            return 0
        mem: list[int] = []
        cohort0 = None
        for cohort, m_sl, cons, j_all, dep, used in log:
            cand = np.nonzero((dep <= t_cut) & ~used)[0]
            for r in cand:
                c = int(cons[r])
                if cons_filter is not None and c != cons_filter:
                    continue
                ch = self._channels.get(c)
                if ch is None:
                    continue
                at = ch["ack_time"][int(j_all[r])]
                at0 = float(at) if np.ndim(at) == 0 else float(at[0])
                if np.isnan(at0) or at0 > t_cut:
                    used[r] = True
                    mem.append(int(m_sl[r]))
                    cohort0 = cohort
        if not mem:
            return 0
        midx = np.asarray(mem, dtype=np.int64)
        dup = dict(cohort0)
        dup["on_seen"] = self._chaos_dup_seen
        lanes = () if self._lanes == 1 else (self._lanes,)
        q["pending"].insert(0, {
            "cohort": dup, "idx": midx,
            "t": np.full((midx.size,) + lanes, float(t_ready)), "pos": 0})
        self._redelivered += midx.size
        return midx.size

    def _chaos_dup_seen(self, mem: np.ndarray, t_done: np.ndarray,
                        cons: np.ndarray) -> None:
        """on_seen for redelivered copies: record duplicate completions
        without overwriting the original consume times."""
        self._dup_times.append(np.asarray(_lane0(np.asarray(t_done)),
                                          dtype=float).copy())
        self._dup_mem.append(np.asarray(mem, dtype=np.int64).copy())

    def _chaos_consumer_down(self, cidx: int, t_evt: float) -> None:
        """Consumer crash: leave the round-robin rotation and requeue the
        crashed channel's unacked deliveries (ready immediately — the
        surviving consumers pick them up, as in the heap broker)."""
        qk = self._chan_queue.get(cidx)
        if qk is None:
            return
        q = self._queues[qk]
        if cidx in q["consumers"]:
            q["consumers"].remove(cidx)
        self._chaos_requeue(qk, t_evt, t_evt, cons_filter=cidx)
        self._pump_queues([qk])

    def _chaos_consumer_up(self, cidx: int) -> None:
        """Consumer respawn: rejoin the rotation (fresh deliveries only,
        like the heap engine's re-register) and pump."""
        qk = self._chan_queue.get(cidx)
        if qk is None:
            return
        q = self._queues[qk]
        if cidx not in q["consumers"]:
            q["consumers"].append(cidx)
        self._pump_queues([qk])

    def _chaos_autoscale_tick(self, t_evt: float) -> None:
        """Backlog-reactive consumer elasticity (the heap engine's
        ``_autoscale_tick``): compare the undelivered work-queue backlog
        to the policy thresholds, grow with fresh consumer ids (never
        reused) or retire the most recent extras gracefully, then
        re-schedule while the run still has events."""
        pol = self._chaos.autoscale
        # ready backlog at the tick clock, from the tracked enqueue /
        # depart counters (non-mutating): enqueues minus everything that
        # departed toward a consumer by t_evt — exactly the heap
        # broker's ``len(q.ready)``
        backlog = 0
        for qk in self._chaos_work_qkeys:
            q = self._queues[qk]
            left = (q["departed"][0]
                    + sum(1 for d in q["depart_heap"][0] if d <= t_evt))
            backlog += int(q["n_enq"][0] - left)
        nq = len(self._chaos_work_qkeys)
        cur = self.spec.n_consumers + len(self._as_extra)
        pumped = []
        if backlog > pol.high_backlog and cur < pol.max_consumers:
            for _ in range(min(pol.step, pol.max_consumers - cur)):
                c = self._as_next
                self._as_next += 1
                self._as_extra.append(c)
                qk = ("work", c % nq)
                self._queues[qk]["consumers"].append(c)
                self._chan_queue[c] = qk
                pumped.append(qk)
        elif backlog < pol.low_backlog and self._as_extra:
            for _ in range(min(pol.step, len(self._as_extra))):
                c = self._as_extra.pop()
                q = self._queues[self._chan_queue[c]]
                if c in q["consumers"]:
                    q["consumers"].remove(c)
        if pumped:
            self._pump_queues(pumped)
        if self._heap:
            self._push_chaos(t_evt + pol.interval_s,
                             self._chaos_autoscale_tick)

    # -- prefetch-windowed delivery (the batched broker pump) ------------------
    def _deliver_queue(self, qkey: tuple, consumers: list[int],
                       t_ready: np.ndarray,
                       member_idx: np.ndarray, combos_fn: Callable,
                       size: int, flow: str, consumer: bool, recv: float,
                       on_seen: Callable) -> None:
        """Enqueue a cohort on one broker queue and pump it through
        ``flow``.

        Deliveries leave the queue in FIFO order; each is assigned to the
        next consumer *with an open basic.qos window* in rotated
        round-robin order (the heap broker's ``next_delivery``), so load
        shifts toward faster/less-congested consumers exactly when windows
        close.  A delivery's depart time is gated on the ack that freed
        its window slot (acks are ack-multiple: a seen message acks every
        lower delivery tag).  ``combos_fn(member_idx, cons)`` builds the
        per-message path-constructor arguments once consumers are known;
        ``on_seen(member_idx, seen_times, cons)`` fires per landed batch —
        partial cohorts are normal."""
        cohort = {"combos_fn": combos_fn, "size": size, "flow": flow,
                  "consumer": consumer, "recv": recv, "on_seen": on_seen}
        q = self._queue_state(qkey, consumers, size)
        o = np.argsort(_lane0(t_ready), kind="stable")
        q["pending"].append({"cohort": cohort, "idx": member_idx[o],
                             "t": t_ready[o], "pos": 0})
        self._pump_queues([qkey])

    def _rr_assign(self, ids: list, t_sl: np.ndarray, P: int
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Strict round-robin split of one whole released segment across
        consumers with open windows (the pump fast path): message ``r``
        goes to ``ids[r % k]`` and its depart gates on the ack that
        freed its basic.qos window slot.  Advances each channel's
        assigned cursor.  Returns ``(consumer_ids, delivery_tags,
        depart_times)``.  The JAX engine overrides the gate/depart
        arithmetic with one fused device computation."""
        n_rem = t_sl.shape[0]
        k = len(ids)
        cons = np.array(ids)[np.arange(n_rem) % k]
        j_all = np.empty(n_rem, dtype=int)
        depart = np.empty(t_sl.shape)
        for r, c in enumerate(ids):
            pos = np.arange(r, n_rem, k)
            ch = self._chan(c)
            self._chan_grow(ch, pos.size)
            j = ch["assigned"] + np.arange(pos.size)
            gate = np.full(t_sl[pos].shape, -np.inf)
            m_g = j >= P
            gate[m_g] = ch["ack_time"][j[m_g] - P]
            j_all[pos] = j
            depart[pos] = np.maximum(t_sl[pos], gate)
            ch["assigned"] += pos.size
        return cons, j_all, depart

    def _pump_queues(self, qkeys: Iterable[tuple]) -> None:
        """Release every window-admissible pending delivery on the given
        queues and push the released groups as transit batches."""
        P = max(1, self.p.prefetch)
        releases: dict[int, list] = {}
        for qk in dict.fromkeys(qkeys):
            q = self._queues[qk]
            ids = q["consumers"]
            while q["pending"]:
                if not ids:
                    # every consumer crashed off this queue (chaos):
                    # backlog waits for a respawn/autoscale pump
                    break
                seg = q["pending"][0]
                n_rem = seg["idx"].size - seg["pos"]
                k = len(ids)
                # fast path: every window stays open through a strict
                # round-robin split of the whole segment remainder
                # (skipped in fine-pump mode — see __init__)
                if not self._fine_pump and \
                        all((P - (self._chan(c)["assigned"]
                                  - self._chan(c)["acked"]))
                            >= (n_rem - r + k - 1) // k
                            for r, c in enumerate(ids)):
                    sl = slice(seg["pos"], seg["pos"] + n_rem)
                    t_sl, m_sl = seg["t"][sl], seg["idx"][sl]
                    cons, j_all, depart = self._rr_assign(ids, t_sl, P)
                    if q.get("outages"):
                        depart = self._chaos_clamp(q, depart)
                    q["consumers"] = ids = ids[n_rem % k:] + ids[:n_rem % k]
                    releases.setdefault(id(seg["cohort"]), []).append(
                        (seg["cohort"], m_sl, cons, j_all, depart))
                    self._record_departs(q, depart)
                    if q.get("log") is not None:
                        q["log"].append(
                            (seg["cohort"], m_sl.copy(), cons.copy(),
                             j_all.copy(),
                             np.asarray(_lane0(depart), dtype=float).copy(),
                             np.zeros(m_sl.size, dtype=bool)))
                    seg["pos"] += n_rem
                    q["pending"].pop(0)
                    continue
                # slow path: per message, the heap broker's next_delivery
                # in virtual time — the first consumer (rotated
                # round-robin) whose basic.qos window is *open at the
                # message's ready time* takes it; with every window
                # closed, the earliest known re-opening (the ack-arrival
                # pump that would pop it) takes the delivery.  This is
                # what shifts load toward less-congested consumers: a
                # consumer behind a saturated NIC acks late, its window
                # stays closed, and the round-robin skips it.  Released
                # in small chunks so ack arrivals (the commits that
                # re-pump this queue) interleave with the assignment.
                rel, ids = self._assign_chunk(seg, ids, P)
                q["consumers"] = ids
                if rel:
                    rel_depart = np.array([r[3] for r in rel])
                    if q.get("outages"):
                        rel_depart = self._chaos_clamp(q, rel_depart)
                    rel_m = np.array([r[0] for r in rel])
                    rel_c = np.array([r[1] for r in rel])
                    rel_j = np.array([r[2] for r in rel])
                    releases.setdefault(id(seg["cohort"]), []).append(
                        (seg["cohort"], rel_m, rel_c, rel_j, rel_depart))
                    self._record_departs(q, rel_depart)
                    if q.get("log") is not None:
                        q["log"].append(
                            (seg["cohort"], rel_m, rel_c, rel_j,
                             np.asarray(_lane0(rel_depart),
                                        dtype=float).copy(),
                             np.zeros(rel_m.size, dtype=bool)))
                if seg["pos"] == seg["idx"].size:
                    q["pending"].pop(0)
                # leave after one slow-path chunk: the commits of what was
                # just released re-pump this queue with a fresh ack clock
                break
        for parts in releases.values():
            cohort = parts[0][0]
            idx = np.concatenate([p[1] for p in parts])
            cons = np.concatenate([p[2] for p in parts])
            j_all = np.concatenate([p[3] for p in parts])
            depart = np.concatenate([p[4] for p in parts])
            self._push_transit(
                depart, cohort["size"], cohort["flow"],
                cohort["combos_fn"](idx, cons),
                on_part=lambda members, t, cohort=cohort, idx=idx,
                cons=cons, j_all=j_all:
                    self._commit(cohort, idx[members], j_all[members],
                                 cons[members], t))

    def _assign_chunk(self, seg: dict, ids: list, P: int
                      ) -> tuple[list, list]:
        """One slow-path assignment chunk: per message, the heap
        broker's ``next_delivery`` in virtual time — the first consumer
        (rotated round-robin) whose basic.qos window is open at the
        message's ready time takes it; with every window closed, the
        earliest known re-opening takes the delivery.  Consumes up to
        ``ack_batch`` messages off ``seg``; returns ``(released,
        rotated_ids)`` where each released entry is ``(member_idx,
        consumer, delivery_tag, depart)``.  The JAX engine overrides
        this with a ``lax.scan`` over the same selection recurrence."""
        chunk = max(1, self.p.ack_batch)
        chans = [self._chan(c) for c in ids]
        # next-assignment window gate per consumer (NaN = the ack that
        # would re-open it hasn't been computed yet); in stacked mode one
        # gate vector per lane, decisions on the pilot lane's column
        gshape = ((len(ids),) if self._lanes == 1
                  else (len(ids), self._lanes))
        g = np.empty(gshape)
        for x, ch in enumerate(chans):
            j = ch["assigned"]
            g[x] = -np.inf if j < P else ch["ack_time"][j - P]
        order = np.arange(len(ids))     # rotated round-robin
        rel = []
        while seg["pos"] < seg["idx"].size and len(rel) < chunk:
            tv = seg["t"][seg["pos"]]
            t = float(_lane0(seg["t"])[seg["pos"]])
            go = g[order]
            go0 = _lane0(go)
            with np.errstate(invalid="ignore"):
                open_pos = np.nonzero(go0 <= t)[0]
            if open_pos.size:
                pos = int(open_pos[0])
            else:
                finite = np.isfinite(go0)
                if not finite.any():
                    break   # re-openings unknown: wait for acks
                pos = int(np.argmin(np.where(finite, go0, np.inf)))
            gate = go[pos]
            x = int(order[pos])
            order = np.append(np.delete(order, pos), x)
            ch = chans[x]
            self._chan_grow(ch, 1)
            j = ch["assigned"]
            ch["assigned"] += 1
            g[x] = (-np.inf if j + 1 < P
                    else ch["ack_time"][j + 1 - P])
            rel.append((seg["idx"][seg["pos"]], ids[x], j,
                        np.maximum(tv, gate)))
            seg["pos"] += 1
        return rel, [ids[x] for x in order]

    def _commit(self, cohort: dict, cidx: np.ndarray, j: np.ndarray,
                chan: np.ndarray, t_land: np.ndarray) -> None:
        """Some released deliveries landed: run the consumer processing
        chains (or stamp producer receive times), advance the channels' ack
        clocks (basic.ack multiple=True — a seen message acks every lower
        tag), and pump deliveries the freed window slots now admit."""
        seen = np.empty_like(t_land)
        recv = cohort["recv"]
        ctrl = self.arch.control_latency_s()
        touched = []
        for c in np.unique(chan):
            m = np.nonzero(chan == c)[0]
            ch = self._chan(c)
            if cohort["consumer"]:
                # serial parse/handle chain on the consumer client
                o = m[np.argsort(_lane0(t_land)[m], kind="stable")]
                proc = self._proc_s * (1.0 + self._jit(o.size))
                ends = self._scan_impl(t_land[o] + recv, proc, ch["free"])
                seen[o] = ends
                ch["free"] = (float(ends[-1]) if ends.ndim == 1
                              else ends[-1].copy())
            else:
                seen[m] = t_land[m] + recv
            ch["seen"][j[m]] = seen[m]
            # batched acks (ack-multiple every ack_batch deliveries, or
            # immediately once the basic.qos window is full)
            B = max(1, self.p.ack_batch)
            P = max(1, self.p.prefetch)
            for mi in m[np.argsort(_lane0(seen)[m], kind="stable")]:
                ch["last_tag"] = max(ch["last_tag"], int(j[mi]) + 1)
                ch["since"] += 1
                if (ch["since"] >= B
                        or ch["assigned"] - ch["acked"] >= P):
                    if ch["last_tag"] > ch["acked"]:
                        ch["ack_time"][ch["acked"]:ch["last_tag"]] = \
                            seen[mi] + ctrl
                        ch["acked"] = ch["last_tag"]
                    ch["since"] = 0
            touched.append(c)
        cohort["on_seen"](cidx, seen, chan)
        self._pump_queues([self._chan_queue[c] for c in touched])

    # -- the one reject-retry / deferred-confirm publish shape ----------------
    def _publish_with_retry(self, members: np.ndarray, t0: np.ndarray, *,
                            flow: str, size: int,
                            combos_of: Callable[[np.ndarray], np.ndarray],
                            groups_of: Callable,
                            deliver: Callable,
                            set_confirms: Optional[Callable] = None,
                            mark_confirmed: Optional[Callable] = None
                            ) -> None:
        """Push a publish cohort through ``flow`` with the full broker
        admission treatment, shared by all four publish legs (work
        publish, feedback reply, broadcast fanout, gather reply):

        * **reject-publish overflow** — members rejected at their target
          queue's byte cap re-enter the publish path after
          ``publish_retry_s`` (the producer re-publish backoff), as a
          retry cohort;
        * **credit-flow deferred confirms** — accepted members that push
          a tracked queue past its credit threshold have their publisher
          confirm withheld on that queue's ``deferred`` list until the
          pump drains it to ``flow_resume`` (only when ``set_confirms``
          is given — reply/gather legs never gate producer windows).

        ``members`` is an opaque index array (positions into whatever
        per-leg arrays the callbacks capture); retries thread subsets of
        it back through ``combos_of``.  ``groups_of(members)`` yields
        ``(group_key, queue_states, positions)`` — one admission group
        per target queue (``len(queue_states) > 1`` = atomic fanout).
        ``deliver(group_key, members, t_enq)`` hands accepted members to
        the delivery pump; ``set_confirms(members, t_conf)`` /
        ``mark_confirmed(members)`` record resolved publisher confirms.

        **Stacked lanes diverge here.**  Admission runs per lane
        (:meth:`_enqueue_batch`), so a member may be admitted in one
        lane and rejected in another.  Scheduling stays the pilot's: a
        member joins a retry cohort iff *lane 0* rejected it (exactly
        the pilot's solo retry schedule — lanes that already admitted it
        keep their frozen admission times and ignore the re-served
        transit); conversely a lane that rejects a pilot-admitted member
        resolves its own retry cadence locally against its own depart
        heap (:meth:`_lane_admit`).  Confirm times, credit blocks and
        reject counts are all per-lane; every lane's ``rejected`` /
        ``blocked`` counters and clocks match what its solo run's
        admission sequence would produce, up to the shared-schedule
        approximation bounded by the stacked-overflow parity tests.
        """
        p = self.p
        ctrl = self.arch.control_latency_s()
        L = self._lanes
        solo = L == 1
        n_state = int(members.max()) + 1 if members.size else 0
        #: per-member per-lane admission state, indexed by member value:
        #: admission time (NaN until admitted), admitted flag, and the
        #: queue whose credit threshold the admission crossed (if any)
        st_t = np.full((n_state, L), np.nan)
        st_in = np.zeros((n_state, L), dtype=bool)
        st_blk = np.full((n_state, L), None, dtype=object)

        def out(a: np.ndarray) -> np.ndarray:
            """Engine-facing view of an ``(m, L)`` time array."""
            return a[:, 0] if solo else a

        def attempt(mem: np.ndarray, t_arr: np.ndarray) -> None:
            def part(mb: np.ndarray, t_enq: np.ndarray) -> None:
                land(mem[mb], t_enq)

            self._push_transit(t_arr, size, flow, combos_of(mem),
                               on_part=part)

        def land(mem: np.ndarray, t_enq: np.ndarray) -> None:
            T = t_enq.reshape(mem.size, L)
            for gkey, queues, pos in groups_of(mem):
                sub = mem[pos]
                already = st_in[sub]
                t_use = np.where(already, st_t[sub], T[pos])
                acc, blocked_on = self._enqueue_batch(queues, t_use,
                                                      skip=already)
                st_t[sub] = np.where(acc, t_use, st_t[sub])
                in_now = already | acc
                st_in[sub] = in_now
                if blocked_on is not None:
                    blk_mask = np.not_equal(blocked_on, None)
                    for r, lane in zip(*np.nonzero(blk_mask)):
                        st_blk[sub[r], lane] = blocked_on[r, lane]
                    self.blocked += blk_mask.sum(axis=0)
                # attempted lanes that stayed out: one reject each
                self.rejected += (~already & ~in_now).sum(axis=0)
                pilot_in = in_now[:, 0]
                rej = np.nonzero(~pilot_in)[0]
                if rej.size:
                    attempt(sub[rej], out(t_use[rej]) + p.publish_retry_s)
                ok = np.nonzero(pilot_in)[0]
                if ok.size == 0:
                    continue
                if not solo:
                    # pilot admitted: the member's schedule is fixed;
                    # lanes that still rejected it resolve their retry
                    # cadence locally against their own depart cursor
                    tracked = [q for q in queues if q["track"]]
                    for k in ok:
                        for lane in np.nonzero(~in_now[k, 1:])[0] + 1:
                            t_adm, extra, bq = self._lane_admit(
                                tracked, lane, float(t_use[k, lane]))
                            self.rejected[lane] += extra
                            st_t[sub[k], lane] = t_adm
                            st_in[sub[k], lane] = True
                            if bq is not None:
                                st_blk[sub[k], lane] = bq
                                self.blocked[lane] += 1
                t_fin = st_t[sub]
                if set_confirms is None:
                    deliver(gkey, sub[ok], out(t_fin[ok]))
                    continue
                if bool(acc.all()) and blocked_on is None:
                    # hot path (no reject, no credit event, anywhere):
                    # bulk confirms, one prefix advance
                    set_confirms(sub, out(t_fin) + ctrl)
                    deliver(gkey, sub, out(t_fin))
                    mark_confirmed(sub)
                    continue
                now = []
                any_deferred = None
                for k in ok:
                    mk = int(sub[k])
                    tc = t_fin[k] + ctrl
                    blk = st_blk[mk]
                    if blk[0] is None:
                        # non-pilot blocked lanes: best-effort resume
                        # clock from the lane's own depart heap, now
                        for lane in range(1, L):
                            if blk[lane] is not None:
                                tc[lane] = max(tc[lane],
                                               self._lane_resume_time(
                                                   blk[lane], lane))
                        set_confirms(np.array([mk]), out(tc[None, :]))
                        now.append(mk)
                    else:
                        # credit flow: withhold this confirm until the
                        # pump drains the pilot's queue to flow_resume;
                        # other blocked lanes read their own resume
                        # clocks when the resolver fires
                        any_deferred = blk[0]

                        def resolver(t_res: float, mk: Any = mk,
                                     tc: Any = tc,
                                     blk: Any = blk) -> None:
                            tv = tc.copy()
                            tv[0] = t_res
                            for lane in range(1, L):
                                if blk[lane] is not None:
                                    tv[lane] = max(
                                        tv[lane], self._lane_resume_time(
                                            blk[lane], lane))
                            set_confirms(np.array([mk]), out(tv[None, :]))
                            mark_confirmed(np.array([mk]))
                        blk[0]["deferred"].append(resolver)
                deliver(gkey, sub[ok], out(t_fin[ok]))
                if now:
                    mark_confirmed(np.asarray(now, dtype=int))
                if any_deferred is not None:
                    self._try_resume(any_deferred)

        attempt(members, t0)

    # -- main ------------------------------------------------------------------
    def _setup(self) -> None:
        """Build the pattern topology and launch the initial publish
        rounds (everything up to draining the event heap)."""
        pat = self.spec.pattern
        if pat in ("work_sharing", "feedback"):
            self._setup_work(feedback=(pat == "feedback"))
        elif pat in ("broadcast", "broadcast_gather"):
            self._setup_broadcast(gather=(pat == "broadcast_gather"))
        else:
            raise ValueError(f"unknown pattern {pat!r}")

    def run(self) -> RunResult:
        if self._lanes > 1:
            raise RuntimeError("this engine was built with stack_seeds; "
                               "use run_stacked()")
        self._setup()
        self._drain_all()
        return self._finalize()

    # -- work sharing (+ feedback) --------------------------------------------
    def _setup_work(self, feedback: bool) -> None:
        spec, p, inv = self.spec, self.p, self.inv
        nP, nC = spec.n_producers, spec.n_consumers
        per_producer = spec.total_messages // nP
        size = spec.workload.payload_bytes
        flush = self.arch.client_flush_s()
        W = max(2, min(p.confirm_window, p.window_bytes // size))

        # declare order matches the heap engine: work queues first (homes
        # round-robin from 0; per-tenant vhost queues in tenant order),
        # then per-producer reply queues
        nq, q_consumers, prod_queues, q_pubs = self._work_topology()
        q_home = np.arange(nq) % inv.n_dsn
        reply_home = (nq + np.arange(nP)) % inv.n_dsn

        pr_node = np.arange(nP) % inv.n_producer_nodes
        pr_bnode = np.arange(nP) % inv.n_dsn
        c_node = np.arange(nC) % inv.n_consumer_nodes
        c_bnode = (np.arange(nC) + 1) % inv.n_dsn

        i_idx = np.broadcast_to(np.arange(per_producer), (nP, per_producer))
        pr_idx = np.broadcast_to(np.arange(nP)[:, None], (nP, per_producer))
        # producer pr round-robins over its own queue list (all queues
        # when shared; its tenant's vhost queues when isolated)
        msg_q = np.empty((nP, per_producer), dtype=int)
        for pr in range(nP):
            ql = np.asarray(prod_queues[pr])
            msg_q[pr] = ql[(pr + np.arange(per_producer)) % ql.size]

        lanes = () if self._lanes == 1 else (self._lanes,)
        confirms = np.zeros((nP, per_producer) + lanes)
        pub_start = np.zeros((nP, per_producer) + lanes)
        consume_t = np.full((nP * per_producer,) + lanes, np.nan)
        rtts = (np.full((nP * per_producer,) + lanes, np.nan)
                if feedback else None)
        recv_req = self._recv_latency(size)
        reply_size = max(1, int(size * p.reply_factor))
        recv_rep = self._recv_latency(reply_size)

        # queue states: work queues see all nP producers' credit, reply
        # queues are exempt from credit flow (the heap engine never
        # withholds reply confirms) but share the byte cap
        cap = (p.queue_max_bytes // size if p.queue_max_bytes else None)
        rcap = (p.queue_max_bytes // reply_size if p.queue_max_bytes
                else None)
        work_q = [self._queue_state(("work", qi), q_consumers[qi], size,
                                    credit=FLOW_CREDIT * q_pubs[qi],
                                    cap_msgs=cap)
                  for qi in range(nq)]
        if feedback:
            for pr in range(nP):
                self._queue_state(("reply", pr), [nC + pr], reply_size,
                                  cap_msgs=rcap)

        R = max(1, min(W, self._round))
        # flow-control events reachable (byte cap below the per-queue
        # volume, a publish surplus that can pile backlog past the
        # credit threshold, or a chaos broker outage that rejects
        # publishes): per-message rounds reproduce the heap engine's
        # burst-and-retry dynamics at the blocking boundary
        if self.p.vec_round is None and (self.flow_events_possible()
                                         or self._chaos_flow_possible()):
            R = 1
        n_rounds = -(-per_producer // R)
        # per-producer resolved-confirm prefixes: round r may launch once
        # every confirm its send gates read (indices < hi - W) is
        # resolved.  Message-granular like the heap engine's confirm
        # window, so a credit-flow deferral stalls exactly the sends it
        # gates — the producers still land W more messages first.
        conf_ok = np.zeros((nP, per_producer), dtype=bool)
        prefix = np.zeros(nP, dtype=np.int64)
        state = {"next_launch": 0}

        def mark_confirmed(pr_arr: np.ndarray,
                           i_arr: np.ndarray) -> None:
            conf_ok[pr_arr, i_arr] = True
            for pr in np.unique(pr_arr):
                j = int(prefix[pr])
                while j < per_producer and conf_ok[pr, j]:
                    j += 1
                prefix[pr] = j
            advance_pubs()

        def advance_pubs() -> None:
            while state["next_launch"] < n_rounds:
                r = state["next_launch"]
                need = min((r + 1) * R, per_producer) - W
                if need > 0 and int(prefix.min()) < need:
                    return
                state["next_launch"] += 1
                launch_pub(r)

        # tenant-aware hop graphs: combos carry the client's tenant as a
        # trailing column (the path constructors' 4th argument)
        tcols = self._tenant_cols
        ppt, cpt = self._ppt, self._cpt

        def _tenant_col(base: np.ndarray, tenant: np.ndarray) -> np.ndarray:
            if not tcols:
                return base
            return np.concatenate([base, tenant[:, None]], axis=1)

        # placement computed from the consumer id, not a presized array:
        # autoscaled consumer ids (nC, nC+1, ... — monotonic, never
        # reused) place exactly like the heap engine's; the tenant
        # column clamps for ids past nC (tenants == 1 there)
        combos_del_by_q = {qi: (lambda mem, cons, qi=qi:
                                _tenant_col(
                                    np.stack([(cons + 1) % inv.n_dsn,
                                              np.full(cons.size, q_home[qi]),
                                              cons % inv.n_consumer_nodes],
                                             axis=1),
                                    np.minimum(cons // cpt,
                                               spec.tenants - 1)))
                           for qi in range(nq)}

        def on_seen_del(mem: np.ndarray, t_done: np.ndarray,
                        cons: np.ndarray) -> None:
            consume_t[mem] = t_done
            if feedback:
                launch_reply(mem, t_done, cons)

        def launch_pub(r: int) -> None:
            lo, hi = r * R, min((r + 1) * R, per_producer)
            i_blk = np.arange(lo, hi)
            gate = np.zeros((nP, i_blk.size) + lanes)
            m_g = i_blk >= W
            gate[:, m_g] = confirms[:, i_blk[m_g] - W]
            s_blk = gate + flush
            pub_start[:, i_blk] = s_blk
            flat_pr = pr_idx[:, i_blk].ravel()
            flat_i = i_idx[:, i_blk].ravel()
            flat_q = msg_q[:, i_blk].ravel()

            def combos_of(mem: np.ndarray) -> np.ndarray:
                return _tenant_col(
                    np.stack([pr_node[flat_pr[mem]],
                              pr_bnode[flat_pr[mem]],
                              q_home[flat_q[mem]]], axis=1),
                    flat_pr[mem] // ppt)

            def groups_of(mem: np.ndarray) -> Iterator[tuple]:
                qs = flat_q[mem]
                for qi in np.unique(qs):
                    yield (int(qi), [work_q[int(qi)]],
                           np.nonzero(qs == qi)[0])

            def set_conf(mem: np.ndarray, t_conf: np.ndarray) -> None:
                confirms[flat_pr[mem], flat_i[mem]] = t_conf

            def mark(mem: np.ndarray) -> None:
                mark_confirmed(flat_pr[mem], flat_i[mem])

            def deliver(qi: int, mem: np.ndarray,
                        t_enq: np.ndarray) -> None:
                self._deliver_queue(
                    ("work", qi), q_consumers[qi], t_enq,
                    flat_pr[mem] * per_producer + flat_i[mem],
                    combos_del_by_q[qi], size, "delivery_path",
                    consumer=True, recv=recv_req, on_seen=on_seen_del)

            self._publish_with_retry(
                np.arange(flat_pr.size),
                s_blk.reshape((nP * i_blk.size,) + lanes),
                flow="publish_path", size=size, combos_of=combos_of,
                groups_of=groups_of, deliver=deliver,
                set_confirms=set_conf, mark_confirmed=mark)

        def launch_reply(members: np.ndarray, t_done: np.ndarray,
                         cons: np.ndarray) -> None:
            # members are global message indices; producer = index // n
            mem_arr, cns_arr = members, cons

            def combos_of(pos: np.ndarray) -> np.ndarray:
                return _tenant_col(
                    np.stack([c_node[cns_arr[pos]],
                              c_bnode[cns_arr[pos]],
                              reply_home[mem_arr[pos] // per_producer]],
                             axis=1),
                    cns_arr[pos] // cpt)

            def groups_of(pos: np.ndarray) -> Iterator[tuple]:
                prs = mem_arr[pos] // per_producer
                for pr in np.unique(prs):
                    yield (int(pr), [self._queues[("reply", int(pr))]],
                           np.nonzero(prs == pr)[0])

            def deliver(pr: int, pos_sel: np.ndarray,
                        t_renq: np.ndarray) -> None:
                def combos_fn(sub_mem: np.ndarray, _cons: np.ndarray,
                              pr: int = pr) -> np.ndarray:
                    row = [reply_home[pr], pr_bnode[pr], pr_node[pr]]
                    if tcols:
                        row.append(pr // ppt)
                    return np.broadcast_to(row, (sub_mem.size, len(row)))

                def on_seen(sub_mem: np.ndarray, t_seen: np.ndarray,
                            _cons: np.ndarray) -> None:
                    flat_pub = pub_start.reshape(
                        (nP * per_producer,) + lanes)
                    rtts[sub_mem] = t_seen - flat_pub[sub_mem]

                self._deliver_queue(
                    ("reply", pr), [nC + pr], t_renq, mem_arr[pos_sel],
                    combos_fn, reply_size, "reply_delivery_path",
                    consumer=False, recv=recv_rep, on_seen=on_seen)

            self._publish_with_retry(
                np.arange(mem_arr.size), t_done,
                flow="reply_publish_path", size=reply_size,
                combos_of=combos_of, groups_of=groups_of, deliver=deliver)

        if self._chaos is not None:
            self._chaos_setup(nq, q_home)
        advance_pubs()
        self._fin_consume, self._fin_rtts = consume_t, rtts
        self._fin_pub = pub_start
        self._fin_confirms = confirms

    # -- broadcast (+ gather) --------------------------------------------------
    def _setup_broadcast(self, gather: bool) -> None:
        spec, p, inv = self.spec, self.p, self.inv
        nC = spec.n_consumers
        assert spec.n_producers == 1, "broadcast patterns use one producer"
        per_producer = spec.total_messages  # // nP with nP == 1
        size = spec.workload.payload_bytes
        flush = self.arch.client_flush_s()
        W = max(2, min(p.confirm_window, p.window_bytes // size))

        bq_home = np.arange(nC) % inv.n_dsn        # bq:c declared in order
        gather_home = nC % inv.n_dsn               # declared after the bqs
        pnode, pbnode = 0 % inv.n_producer_nodes, 0
        c_node = np.arange(nC) % inv.n_consumer_nodes
        c_bnode = (np.arange(nC) + 1) % inv.n_dsn

        lanes = () if self._lanes == 1 else (self._lanes,)
        confirms = np.zeros((per_producer,) + lanes)
        pub_start = np.zeros((per_producer,) + lanes)
        consume_t = np.full((per_producer * nC,) + lanes, np.nan)
        rtts = (np.full((per_producer * nC,) + lanes, np.nan)
                if gather else None)
        recv_req = self._recv_latency(size)
        reply_size = max(1, int(size * p.reply_factor))
        recv_rep = self._recv_latency(reply_size)

        # fanout targets: reject-publish is atomic across all of them, and
        # the first flow-blocked target withholds the confirm (heap broker)
        cap = (p.queue_max_bytes // size if p.queue_max_bytes else None)
        rcap = (p.queue_max_bytes // reply_size if p.queue_max_bytes
                else None)
        bqs = [self._queue_state(("bq", c), [c], size,
                                 credit=FLOW_CREDIT, cap_msgs=cap)
               for c in range(nC)]
        if gather:
            self._queue_state(("gather",), [nC], reply_size, cap_msgs=rcap)

        R = max(1, min(W, self._round))
        # flow-control events reachable on the fanout targets: see
        # _setup_work
        if self.p.vec_round is None and self.flow_events_possible():
            R = 1
        n_rounds = -(-per_producer // R)
        # resolved-confirm prefix of the single producer (see _run_work)
        conf_ok = np.zeros(per_producer, dtype=bool)
        state = {"next_launch": 0, "prefix": 0}

        def mark_confirmed(i_arr: np.ndarray) -> None:
            conf_ok[i_arr] = True
            j = state["prefix"]
            while j < per_producer and conf_ok[j]:
                j += 1
            state["prefix"] = j
            advance_pubs()

        def advance_pubs() -> None:
            while state["next_launch"] < n_rounds:
                r = state["next_launch"]
                need = min((r + 1) * R, per_producer) - W
                if need > 0 and state["prefix"] < need:
                    return
                state["next_launch"] += 1
                launch_pub(r)

        def launch_pub(r: int) -> None:
            lo, hi = r * R, min((r + 1) * R, per_producer)
            i_blk = np.arange(lo, hi)
            gate = np.zeros((i_blk.size,) + lanes)
            m_g = i_blk >= W          # rounds can straddle the window edge
            gate[m_g] = confirms[i_blk[m_g] - W]
            s_blk = gate + flush
            pub_start[i_blk] = s_blk

            def combos_of(mem: np.ndarray) -> np.ndarray:
                # a fanout publish transits once, to the exchange's home
                return np.broadcast_to([pnode, pbnode, 0], (mem.size, 3))

            def groups_of(mem: np.ndarray) -> Iterator[tuple]:
                # one admission group: reject-publish and credit flow are
                # atomic across every fanout target (heap broker)
                yield None, bqs, np.arange(mem.size)

            def set_conf(mem: np.ndarray, t_conf: np.ndarray) -> None:
                confirms[i_blk[mem]] = t_conf

            def mark(mem: np.ndarray) -> None:
                mark_confirmed(i_blk[mem])

            def deliver(_g: object, mem: np.ndarray,
                        t_enq: np.ndarray) -> None:
                launch_del(i_blk[mem], t_enq)

            self._publish_with_retry(
                np.arange(i_blk.size), s_blk, flow="publish_path",
                size=size, combos_of=combos_of, groups_of=groups_of,
                deliver=deliver, set_confirms=set_conf,
                mark_confirmed=mark)

        def launch_del(i_part: np.ndarray, t_enq: np.ndarray) -> None:
            # replicate to every per-consumer queue; deliver each copy
            for c in range(nC):
                gidx_c = c * per_producer + i_part

                def combos_fn(members: np.ndarray, cons: np.ndarray,
                              c: int = c) -> np.ndarray:
                    return np.broadcast_to(
                        [c_bnode[c], bq_home[c], c_node[c]],
                        (members.size, 3))

                def on_seen(members: np.ndarray, t_done: np.ndarray,
                            cons: np.ndarray, c: int = c) -> None:
                    consume_t[members] = t_done
                    if gather:
                        launch_reply(members, t_done, c)

                self._deliver_queue(
                    ("bq", c), [c], t_enq, gidx_c, combos_fn, size,
                    "delivery_path", consumer=True, recv=recv_req,
                    on_seen=on_seen)

        def launch_reply(members: np.ndarray, t_done: np.ndarray,
                         c: int) -> None:
            # members are global copy indices (c * per_producer + i)
            mem_arr = members

            def combos_of(pos: np.ndarray) -> np.ndarray:
                return np.broadcast_to(
                    [c_node[c], c_bnode[c], gather_home], (pos.size, 3))

            def groups_of(pos: np.ndarray) -> Iterator[tuple]:
                yield None, [self._queues[("gather",)]], np.arange(pos.size)

            def deliver(_g: object, pos_sel: np.ndarray,
                        t_renq: np.ndarray) -> None:
                def combos_fn(sub_members: np.ndarray,
                              _cons: np.ndarray) -> np.ndarray:
                    return np.broadcast_to(
                        [gather_home, pbnode, pnode],
                        (sub_members.size, 3))

                def on_seen(sub_members: np.ndarray, t_seen: np.ndarray,
                            _cons: np.ndarray) -> None:
                    rtts[sub_members] = (
                        t_seen - pub_start[sub_members % per_producer])

                self._deliver_queue(
                    ("gather",), [nC], t_renq, mem_arr[pos_sel],
                    combos_fn, reply_size, "reply_delivery_path",
                    consumer=False, recv=recv_rep, on_seen=on_seen)

            self._publish_with_retry(
                np.arange(mem_arr.size), t_done,
                flow="reply_publish_path", size=reply_size,
                combos_of=combos_of, groups_of=groups_of, deliver=deliver)

        advance_pubs()
        self._fin_consume, self._fin_rtts = consume_t, rtts
        self._fin_pub = pub_start
        self._fin_confirms = confirms

    # -- shared result assembly ------------------------------------------------
    def _finalize(self) -> RunResult:
        """Assemble the RunResult from the state ``_setup_*`` recorded
        (split from :meth:`run` so the stacked path can drain before
        finalizing each lane)."""
        return self._result(self.spec, self._fin_consume, self._fin_rtts,
                            self._fin_pub.reshape(-1))

    def _finalize_stacked(self) -> list:
        """Per-lane results of a stacked run: lane ``s`` is the cell run
        with ``stack_seeds[s]``.  Flow-control counters (rejected /
        blocked confirms) are lane-resolved — each lane's own admission
        decisions against its own credit backlog and depart cursor; only
        the event count is a scheduling-level quantity shared by all
        lanes (the pilot's cohorts)."""
        import dataclasses
        pub = self._fin_pub.reshape(-1, self._lanes)
        out = []
        for s, seed in enumerate(self.stack_seeds):
            spec_s = dataclasses.replace(
                self.spec, params=dataclasses.replace(self.p, seed=seed))
            out.append(self._result(
                spec_s, self._fin_consume[:, s],
                None if self._fin_rtts is None else self._fin_rtts[:, s],
                pub[:, s], lane=s))
        return out

    def run_stacked(self) -> list:
        """Run all ``stack_seeds`` lanes in one batched event loop and
        return their per-lane results (in ``stack_seeds`` order).

        The pilot lane (``stack_seeds[0]``) is bit-identical to a solo
        :meth:`run` of the same spec — it drives every scheduling
        decision with its own clock, including every broker admission
        decision (reject-publish, credit blocking).  The other lanes run
        the *same schedule* (cohort splits, delivery assignment, chunk
        boundaries) with their own jitter streams, resources, FIFO
        carries, **and their own flow-control accounting**: per-lane
        credit backlogs, depart cursors, reject-retry cadences and
        deferred-confirm clocks (see :meth:`_publish_with_retry`), so
        per-lane rejected/blocked counters are lane-resolved even in the
        overflow regime.  Non-overflow lanes deviate from a solo run by
        the same ordering-slack class of approximation as
        ``vec_horizon_s`` (well under 1% on aggregate summaries, see
        tests/test_campaign.py); overflow-regime lanes stay within 5% of
        their solo heap runs (tests/test_engine_parity.py)."""
        if self._lanes == 1:
            return [self.run()]
        self._setup()
        self._drain_all()
        return self._finalize_stacked()

    def _result(self, spec: ExperimentSpec, consume_t: np.ndarray,
                rtts: Optional[np.ndarray],
                pub_start: np.ndarray, lane: int = 0) -> RunResult:
        # arrays are indexed pr*per_producer + i (work patterns) or
        # c*per_producer + i (broadcast), so producer attribution falls
        # out of the finite-entry indices
        fin_c = np.isfinite(consume_t)
        consume_t = consume_t[fin_c]
        # NaN marks a message with no reply to wait for; +inf (a reply
        # the device program never received) stays, so it shows
        fin_r = ~np.isnan(rtts) if rtts is not None else None
        r = rtts[fin_r] if rtts is not None else np.zeros(0)
        per_producer = max(1, spec.total_messages // spec.n_producers)
        if spec.pattern.startswith("broadcast"):
            cp = np.zeros(consume_t.size, dtype=np.int64)
            rp = np.zeros(r.size, dtype=np.int64)
        else:
            cp = np.flatnonzero(fin_c) // per_producer
            rp = (np.flatnonzero(fin_r) // per_producer
                  if fin_r is not None else np.zeros(0, dtype=np.int64))
        if self._dup_times:
            # chaos redelivery: duplicated completions join the consume
            # stream (at-least-once — the heap engine records them the
            # same way); chaos runs solo, so lane == 0 always here
            dup_t = np.concatenate(self._dup_times)
            dup_m = np.concatenate(self._dup_mem)
            fin_d = np.isfinite(dup_t)
            consume_t = np.concatenate([consume_t, dup_t[fin_d]])
            cp = np.concatenate([cp, dup_m[fin_d] // per_producer])
        top = float(consume_t.max()) if consume_t.size else 0.0
        if r.size:
            top = max(top, float(r.max()))
        return RunResult(
            spec=spec, feasible=True,
            consume_times=consume_t,
            rtts=r,
            publish_starts=np.sort(pub_start),
            rejected_publishes=int(self.rejected[lane]),
            blocked_confirms=int(self.blocked[lane]),
            redelivered=int(self._redelivered),
            sim_time=top, n_events=self.n_events,
            consume_producers=cp, rtt_producers=rp, path=self.PATH)


ENGINES["vectorized"] = VectorizedStreamSim


# ---------------------------------------------------------------------------
# Stacked multi-run execution (the campaign layer's batched entry point)
# ---------------------------------------------------------------------------


def _stack_key(spec: ExperimentSpec) -> tuple:
    """Cells that differ only in ``params.seed`` stack into one run."""
    import dataclasses
    return (spec.pattern, spec.arch, spec.workload, spec.n_producers,
            spec.n_consumers, spec.total_messages,
            getattr(spec, "tenants", 1),
            getattr(spec, "tenant_isolation", "shared"),
            repr(sorted(dataclasses.replace(
                spec.params, seed=0).__dict__.items())))


#: stacked lanes per run are chunked to bound the array working set
STACK_MAX_LANES = 16


@spans.traced("streamsim.run_many")
def run_many(specs: Sequence[ExperimentSpec],
             inventory: Optional[ClusterInventory] = None
             ) -> list[RunResult]:
    """Run several experiments, stacking structurally-identical cells.

    The campaign layer's batched entry point: cells that differ only in
    their seed (the paper's 3-run averaging, or wider seed sweeps) are
    grouped and pushed through one :meth:`VectorizedStreamSim.run_stacked`
    event loop as stacked cohort lanes — the batched run costs barely
    more than a single solo run, instead of ``n_seeds`` times as much.
    This includes overflow-regime cells (explicit ``queue_max_bytes``
    caps, credit-flow-reachable publish surpluses): flow control is
    lane-resolved, so each lane carries its own reject/block counters
    and admission clocks.  Only heterogeneous cells (different
    pattern/arch/consumer-count/knobs) and heap-engine cells fall back
    to per-cell solo execution.

    ``engine="jax"`` cells stack the same way (the JAX engine shares the
    stacked-lane contract); cells the JAX engine cannot take — JAX not
    importable, or an unsupported cell shape — fall back to the
    vectorized engine, recorded per cell in the result's
    ``spec.params.engine`` (campaign summaries surface it as
    ``Summary.engine``).

    Infeasible specs come back as ``feasible=False`` results, like
    :func:`~repro.core.simulator.run_experiment`.  Returns one
    :class:`RunResult` per spec, in input order.

    Engines the whole-run device program takes are handed to
    :func:`~repro.core.jax_device_loop.run_wave_cells` lazily: it
    enqueues the first one's program before the next engine is
    constructed, so the later groups' engines and static builds (and
    the groups run here on the host) overlap the device's work."""
    import dataclasses

    from repro.core.simulator import get_engine, run_experiment
    specs = list(specs)
    results: list = [None] * len(specs)
    for i, spec in enumerate(specs):
        if spec.params.engine == "jax":
            from repro.core import jax_engine
            ok, _why = jax_engine.jax_supported(spec)
            if not ok:
                specs[i] = dataclasses.replace(
                    spec, params=dataclasses.replace(
                        spec.params, engine="vectorized"))
    groups: dict = {}
    for i, spec in enumerate(specs):
        if (spec.params.engine in ("vectorized", "jax")
                and spec.params.chaos is None):
            # engine is part of params, so the key never mixes engines;
            # chaos cells never stack (epoch state is per-run mutable)
            groups.setdefault(_stack_key(spec), []).append(i)
        else:
            groups[("solo", i)] = [i]
    deferred: list = []     # the chunks of the engines yielded, in order

    def device_sims() -> Iterator:
        """Run every group the device program does not take; yield the
        engines of those it does, each constructed only when the device
        loop asks for its next cell."""
        for idxs in groups.values():
            stack = len(idxs) > 1
            if not stack:
                for i in idxs:
                    results[i] = run_experiment(specs[i], inventory)
                continue
            cls = get_engine(specs[idxs[0]].params.engine)
            # one probe per group: feasibility is structural, identical
            # across the seeds
            try:
                with spans.span("streamsim.engine"):
                    cls(specs[idxs[0]], inventory)
                spans.count("streamsim.engines", 1)
            except InfeasibleConfiguration as e:
                for i in idxs:
                    results[i] = RunResult(spec=specs[i], feasible=False,
                                           infeasible_reason=str(e))
                continue
            max_lanes = getattr(cls, "STACK_MAX_LANES", STACK_MAX_LANES)
            for lo in range(0, len(idxs), max_lanes):
                chunk = idxs[lo:lo + max_lanes]
                if len(chunk) == 1:
                    results[chunk[0]] = run_experiment(specs[chunk[0]],
                                                       inventory)
                    continue
                seeds = [specs[i].params.seed for i in chunk]
                with spans.span("streamsim.engine"):
                    sim = cls(specs[chunk[0]], inventory, stack_seeds=seeds)
                spans.count("streamsim.engines", 1)
                if getattr(sim, "_use_device_loop", lambda: False)():
                    # whole-run device programs batch across *cells* too
                    # (vmap-over-cells; see repro.core.jax_device_loop)
                    deferred.append(chunk)
                    yield sim
                    continue
                for i, r in zip(chunk, sim.run_stacked()):
                    results[i] = r

    sims = device_sims()
    first = next(sims, None)
    if first is not None:
        from repro.core import jax_device_loop
        lane_results = jax_device_loop.run_wave_cells(
            itertools.chain([first], sims))
        for chunk, rs in zip(deferred, lane_results):
            for i, r in zip(chunk, rs):
                results[i] = r
    return results


