"""Whole-run JAX device programs for the StreamSim *wave* regime.

The vectorized engine runs a cohort event loop in Python.  For the
regime every deployment-grid cell lives in, this module compiles the
**entire run** — admission gating by publisher-confirm windows,
hop-graph resource FIFO serving, the windowed broker pump with prefetch
gates and batched ack-multiples, feedback replies — into one
``lax.scan`` over *generations* of messages, with stacked seed-lanes
vmapped and whole cells batched by :func:`run_wave_cells` (a
``vmap`` over cells).  ``engine="jax"`` with
``jax_device_loop=True`` runs it (:mod:`repro.core.jax_engine`).

**The wave contract.**  The device program is *not* the event loop — it
is a wave-synchronous re-formulation that is exact where the regime
makes exactness cheap and banded where it does not:

* Messages advance in per-producer *generations* of ``G`` messages
  (``G <= min(confirm_window, prefetch // 2)``, shrunk until no consumer
  can see more than ``prefetch // 2`` deliveries per generation).  A
  generation's sends are gated by the confirm ring exactly like the
  engines' confirm window (message ``i`` waits on confirm ``i - W``).
* Every shared resource keeps per-chain FIFO carries across generations
  (pipes: one chain; pools: ``k`` interleaved chains with per-serve
  earliest-free ordering — the vectorized engine's pool semantics), so
  capacity/work conservation is exact and throughput parity holds at
  the vectorized engine's own band.
* Work is ordered across scan steps by step, inside one by arrival:
  a step's legs share one combined serve.  Feedback replies are not
  scheduled ahead: each waits in a pending-reply buffer in the scan
  carry until the step whose request-leg clocks have reached it
  (:func:`_reply_admit`), and drain steps serve what is left.  So
  latency-sensitive metrics (RTT) on **saturated** cells carry a wider
  tolerance than the cohort engines (see ``repro.core.parity``
  ``device_loop.*`` bands and docs/engines.md).
* Acks flush at every ``ack_batch`` boundary *and* at generation end
  (the engines flush on prefetch pressure instead); jitter draws are
  re-realized per lane from the same per-seed streams (identical
  distribution, different realization than the cohort engines).

**Backends.**  The whole program is written once against a tiny ``ops``
namespace with two implementations: ``jax`` (``lax.scan`` +
``associative_scan`` segmented FIFO closed forms, jitted under
:func:`repro.compat.scoped_x64`) and ``numpy`` (a
plain Python generation loop over the *same* step function).  The NumPy
backend is the step-for-step oracle: ``tests/test_flow_control_props.py``
property-tests that both backends produce the same per-generation trace.

**One pump.**  The pump window assignment (round-robin consumer pick +
prefetch-ring gate + depart clamp) is the XLA closed form
:func:`_pump_assign` on every backend.  The clocks are float64 by
contract (:func:`repro.compat.scoped_x64`), and Mosaic
compiles no 64-bit operands, so a Pallas port cannot compile for a TPU
while that contract stands.

Pad-and-mask: member axes pad to the next power of two with invalid
members carrying ``+inf`` clocks, zero holds and dummy carry chains —
inert under every op (``tests/test_jax_engine.py`` property-tests the
ops against their NumPy backend with such pads appended).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from collections.abc import Iterable, Iterator, Mapping
from typing import Any, Callable, Optional

import numpy as np

from repro.core import spans
from repro.core.simulator import ExperimentSpec
from repro.core.vectorized import VectorizedStreamSim, _align_paths

Array = Any

_INF = np.inf
#: integer sentinel for "no next ack boundary" (never survives: the last
#: valid member of every consumer segment is always a boundary)
_IBIG = np.int64(2) ** 40


# ---------------------------------------------------------------------------
# Regime gate
# ---------------------------------------------------------------------------


def device_loop_supported(spec: ExperimentSpec) -> tuple[bool, str]:
    """Can the whole-run device program take this cell?  ``(ok, why)``.

    Requires JAX, a work-sharing/feedback pattern, a statically
    flow-event-free regime (no credit blocking / reject-publish
    reachable — the wave program carries no retry machinery), a
    generation size that keeps every consumer under half its prefetch
    per generation, and for feedback a confirm window that never binds,
    a square fleet of 32 to 96 producers and a run of at least 32
    messages per producer.  Unsupported cells run on the vectorized
    engine (``RunResult.path`` says which ran); this is a *request*, not
    a demand."""
    from repro.core.jax_engine import jax_available
    from repro.core.simulator import InfeasibleConfiguration
    if not jax_available():
        return False, "jax is not importable in this environment"
    if spec.pattern not in ("work_sharing", "feedback"):
        return False, f"pattern {spec.pattern!r} is not wave-formulated"
    try:
        sim = VectorizedStreamSim(spec)
    except InfeasibleConfiguration as e:
        return False, f"infeasible deployment: {e}"
    return _device_loop_ok(sim)


def _device_loop_ok(sim: VectorizedStreamSim) -> tuple[bool, str]:
    spec, p = sim.spec, sim.p
    M = spec.total_messages // max(1, spec.n_producers)
    if spec.pattern not in ("work_sharing", "feedback"):
        return False, f"pattern {spec.pattern!r} is not wave-formulated"
    if spec.total_messages // max(1, spec.n_producers) < 1:
        return False, "fewer messages than producers"
    if sim.flow_events_possible():
        return False, ("flow-control events (credit blocking / overflow) "
                       "are reachable; the wave program models neither")
    G = _pick_generation(sim)
    if G is None:
        return False, ("no generation size keeps every consumer under "
                       "prefetch/2 deliveries per generation")
    # Universal run-length clause (any pattern): the wave schedule's
    # lockstep generation barriers accumulate against the cohort
    # loop's continuous pipelining, so throughput deviation grows with
    # msgs/producer regardless of the confirm window or jitter
    # (measured on work_sharing dts c8: 0.4% at 128, 3.9% at 256,
    # 6.8% at 512, 8.1% at 1024 msgs/producer — crossing the 6% band
    # between 256 and 512).  Every validated cell (bench e2e rows,
    # parity suites, the calibration grid) sits at <= 256.
    if M > 256:
        return False, (f"run length {M} msgs/producer > 256: "
                       "generation-barrier drift accumulates over long "
                       "runs (throughput deviation grows with nGen past "
                       "the parity band)")
    if spec.pattern == "feedback":
        # A reply waits in the pending-reply buffer until the publishes'
        # clock reaches it (_reply_admit), which keeps replies and
        # publishes in arrival order at the ingress.  Its delivery comes
        # at the earliest one step later, so where replies queue behind a
        # long ingress backlog they reach the egress only after request
        # deliveries that arrive there later than they do: the last
        # deliveries finish early and the replies late.  The regime below
        # is where that stays inside the device_loop bands, measured
        # against the vectorized engine (CPU, seeds 0-1, jitter 0.03; the
        # grids are in docs/engines.md, "Supported regime").
        size = spec.workload.payload_bytes
        W = max(2, min(p.confirm_window, p.window_bytes // size))
        if M > W:
            # +11% throughput at Fig 6 c16 on dts (192 msgs/producer,
            # W=128), -19% median RTT at 4x2 / 1024 msgs / W=32, -11%
            # throughput at 32x32 on prs-haproxy (256 msgs, W=128)
            return False, (f"feedback with a binding confirm window "
                           f"(W={W} < {M} msgs/producer): the reply "
                           "schedule drifts from the cohort loop by up to "
                           "11% throughput and 19% median RTT")
        nP, nC = spec.n_producers, spec.n_consumers
        if nP != nC or not 32 <= nP <= 96:
            # square fleets below 32: +9% to +12% throughput on dts at
            # 8x8 and 16x16, +10% to +16% on mss at 4x2, +15% median RTT
            # on prs-haproxy at 4x2; at 128x128, -12% on mss and +9% on
            # dts; producers != consumers: -19% to +27% on dts at 32 or
            # 48 producers over 8 to 128 consumers (48 msgs/producer or
            # more)
            return False, (f"feedback on a {nP} x {nC} fleet: the reply "
                           "schedule follows the cohort loop only with "
                           "as many consumers as producers, 32 to 96 of "
                           "each (elsewhere up to 27% throughput and 15% "
                           "median RTT off)")
        if M < 32:
            # -10% (dts) to -16% (prs-haproxy) throughput at 64x64 with
            # 16 msgs/producer
            return False, (f"feedback run of {M} msgs/producer < 32: too "
                           "short for the reply schedule (up to 16% "
                           "throughput off the cohort loop)")
    return True, ""


# ---------------------------------------------------------------------------
# Static build: topology -> arrays
# ---------------------------------------------------------------------------


def _pick_generation(sim: VectorizedStreamSim) -> Optional[int]:
    """Largest workable generation size G.

    Upper bounds: the cohort engines' publish *round* (the wave's
    phase interleaving granularity — publishes of one generation serve
    before deliveries of the previous at shared resources, exactly the
    convoy the vectorized engine exhibits per round, so matching its
    round keeps the distortion inside the vectorized engine's own
    bands); the confirm window; and a per-consumer load of at most
    prefetch//2 per generation, so prefetch gates always resolve
    against *earlier* generations' ack rings."""
    spec, p = sim.spec, sim.p
    nP = spec.n_producers
    size = spec.workload.payload_bytes
    W = max(2, min(p.confirm_window, p.window_bytes // size))
    nq, q_consumers, prod_queues, _ = sim._work_topology()
    budget = max(1, p.prefetch // 2)
    rnd = max(1, int(getattr(sim, "_round", 8)))
    for G in range(min(W, budget, rnd), 0, -1):
        # per-queue arrivals per generation: every producer publishing
        # into the queue lands at most ceil(G * |its queues touching q|)
        # ... message routing is round-robin, so producer pr sends at
        # most ceil(G / len(prod_queues[pr])) of a generation to q
        load_ok = True
        for qi in range(nq):
            arrivals = sum(-(-G // len(prod_queues[pr]))
                           for pr in range(nP) if qi in prod_queues[pr])
            per_consumer = -(-arrivals // max(1, len(q_consumers[qi])))
            if per_consumer > budget:
                load_ok = False
                break
        if load_ok and G <= budget:
            return G
    return None


def _path_slots(paths: dict, res_index: dict, kinds: dict,
                size: int) -> tuple[dict, int]:
    """Resolve + align a {combo_key: [PathElement]} map into per-combo
    per-slot static tuples ``(kind, rid, hold_base, lat)`` where kind is
    0 latency-only / 1 pipe / 2 pool."""
    aligned, n_slots = _align_paths(paths)
    out = {}
    for key, els in aligned.items():
        rows = []
        for el in els:
            if el is None or el.resource is None:
                rows.append((0, 0, 0.0,
                             0.0 if el is None else el.latency_s))
                continue
            spec = res_index[el.resource]
            nbytes = size * el.byte_factor + el.extra_bytes
            if spec.kind == "pipe":
                hold = spec.service_s + (
                    nbytes / spec.rate_Bps if spec.rate_Bps else 0.0)
                rows.append((1, kinds[el.resource], hold, el.latency_s))
            else:
                hold = spec.service_s + nbytes * spec.per_byte_s
                rows.append((2, kinds[el.resource], hold, el.latency_s))
        out[key] = rows
    return out, n_slots


@dataclasses.dataclass
class WaveStatic:
    """Everything the device program needs, as NumPy arrays + a
    hashable ``signature`` (the compile/vmap-batching bucket)."""

    meta: dict                 # hashable ints/flags/pool layout
    xs: dict                   # per-generation arrays, leading axis nGen
    inv: dict                  # loop-invariant arrays (tables, scalars)
    sizes: dict                # python ints used by the host wrapper

    def signature(self) -> tuple:
        return (tuple(sorted(self.meta.items())),
                tuple(sorted((k, v.shape, str(v.dtype))
                             for k, v in self.xs.items())),
                tuple(sorted((k, v.shape, str(v.dtype))
                             for k, v in self.inv.items())))


def _crossed(tab: np.ndarray, every: bool = False) -> set:
    """Resource ids that some path of ``tab`` holds, or with ``every``
    that each path holds (rows holding none are padding)."""
    paths = tab.reshape((-1,) + tab.shape[-2:])
    held = paths[..., 0] > 0
    live = held.any(axis=1)
    if not live.any():
        return set()
    rid = paths[..., 1].astype(np.int64)
    on = np.zeros((paths.shape[0], int(rid.max()) + 1), dtype=bool)
    p_i, s_i = np.nonzero(held)
    on[p_i, rid[p_i, s_i]] = True
    return set(np.flatnonzero(on[live].all(axis=0) if every
                              else on.any(axis=0)).tolist())


@spans.traced("streamsim.build_static")
def build_static(sim: VectorizedStreamSim) -> WaveStatic:
    """Extract the wave program's static schedule from a constructed
    (but not yet run) engine instance."""
    spec, p, inv = sim.spec, sim.p, sim.inv
    arch = sim.arch
    nP, nC = spec.n_producers, spec.n_consumers
    M = spec.total_messages // nP
    size = spec.workload.payload_bytes
    reply_size = max(1, int(size * p.reply_factor))
    feedback = spec.pattern == "feedback"
    W = max(2, min(p.confirm_window, p.window_bytes // size))
    G = _pick_generation(sim)
    assert G is not None, "call _device_loop_ok first"
    G = min(G, M)
    nGen = -(-M // G)
    L = sim._lanes

    nq, q_consumers, prod_queues, _ = sim._work_topology()
    q_home = np.arange(nq) % inv.n_dsn
    reply_home = (nq + np.arange(nP)) % inv.n_dsn
    pr_node = np.arange(nP) % inv.n_producer_nodes
    pr_bnode = np.arange(nP) % inv.n_dsn
    c_node = np.arange(nC) % inv.n_consumer_nodes
    c_bnode = (np.arange(nC) + 1) % inv.n_dsn
    tcols = sim._tenant_cols
    ppt, cpt = sim._ppt, sim._cpt

    # resource registry: flat chain ids (pipes 1 chain, pools k chains)
    res_keys = sorted(sim.arch.resources)
    res_index = {k: sim.arch.resources[k] for k in res_keys}
    rid_of = {k: i for i, k in enumerate(res_keys)}
    NR = len(res_keys)
    k_arr = np.ones(NR, dtype=np.int64)
    chain_base = np.zeros(NR, dtype=np.int64)
    pools = []
    base = 0
    for k in res_keys:
        s = res_index[k]
        kk = max(1, s.servers) if s.kind == "pool" else 1
        chain_base[rid_of[k]] = base
        k_arr[rid_of[k]] = kk
        if s.kind == "pool":
            pools.append((base, kk))
        base += kk
    NCH = base                         # +1 dummy row appended by backends

    def tkey(t: int) -> tuple:
        return (t,) if tcols else ()

    # -- publish paths: one combo per (pr, q), aligned together ----------
    pub_paths = {}
    for pr in range(nP):
        for qi in prod_queues[pr]:
            pub_paths[(pr, qi)] = arch.publish_path(
                int(pr_node[pr]), int(pr_bnode[pr]), int(q_home[qi]),
                *tkey(pr // ppt))
    pub_slots, S_pub = _path_slots(pub_paths, res_index, rid_of, size)
    pub_keys = sorted(pub_slots)
    pub_idx_of = {k: i for i, k in enumerate(pub_keys)}
    pub_tab = np.zeros((len(pub_keys), S_pub, 4))
    for k, rows in pub_slots.items():
        pub_tab[pub_idx_of[k]] = rows

    # -- delivery paths: aligned per queue (like _deliver_queue batches),
    #    padded to the max slot count with inert latency-only slots -----
    del_aligned = {}
    S_del = 0
    for qi in range(nq):
        dp = {int(c): arch.delivery_path(
            int(c_bnode[c]), int(q_home[qi]), int(c_node[c]),
            *tkey(int(c) // cpt)) for c in q_consumers[qi]}
        slots, ns = _path_slots(dp, res_index, rid_of, size)
        del_aligned[qi] = slots
        S_del = max(S_del, ns)
    kq = np.array([len(q_consumers[qi]) for qi in range(nq)],
                  dtype=np.int64)
    kq_max = int(kq.max())
    q_cons_tab = np.zeros((nq, kq_max), dtype=np.int64)
    del_tab = np.zeros((nq, kq_max, S_del, 4))
    for qi in range(nq):
        for j, c in enumerate(q_consumers[qi]):
            q_cons_tab[qi, j] = int(c)
            rows = del_aligned[qi][int(c)]
            del_tab[qi, j, :len(rows)] = rows

    # -- reply paths (feedback) -----------------------------------------
    if feedback:
        # a reply-publish path depends on the consumer's node, broker and
        # tenant and the producer's reply home only: each distinct one is
        # built and aligned once (alignment reads the distinct paths)
        rp_key = {(int(c), pr): (int(c_node[c]), int(c_bnode[c]),
                                 int(reply_home[pr])) + tkey(int(c) // cpt)
                  for pr in range(nP)
                  for c in sorted({int(x) for qi in prod_queues[pr]
                                   for x in q_consumers[qi]})}
        keys = sorted(set(rp_key.values()))
        rp_slots, S_rp = _path_slots(
            {k: arch.reply_publish_path(*k) for k in keys}, res_index,
            rid_of, reply_size)
        k_of = {k: i for i, k in enumerate(keys)}
        cp = np.array(list(rp_key), dtype=np.int64).reshape(-1, 2)
        rp_tab = np.zeros((nC, nP, S_rp, 4))
        rp_tab[cp[:, 0], cp[:, 1]] = np.array(
            [rp_slots[k] for k in keys])[[k_of[k] for k in rp_key.values()]]
        rd_aligned = {}
        S_rd = 0
        for pr in range(nP):
            slots, ns = _path_slots(
                {0: arch.reply_delivery_path(
                    int(reply_home[pr]), int(pr_bnode[pr]),
                    int(pr_node[pr]), *tkey(pr // ppt))},
                res_index, rid_of, reply_size)
            rd_aligned[pr] = slots[0]
            S_rd = max(S_rd, ns)
        rd_tab = np.zeros((nP, S_rd, 4))
        for pr in range(nP):
            rows = rd_aligned[pr]
            rd_tab[pr, :len(rows)] = rows
    else:
        S_rp = S_rd = 0
        rp_tab = np.zeros((nC, nP, 0, 4))
        rd_tab = np.zeros((nP, 0, 4))

    # combined-serve slot axis: all legs pad to one width so each
    # step's transits run as a SINGLE serve over the concatenated
    # member axis (shared resources then see competing flows in true
    # arrival order); the extra slots are kind-0 inert pass-throughs
    S_max = max(S_pub, S_del, S_rp, S_rd)

    def pad_slots(tab: np.ndarray) -> np.ndarray:
        pad = ([(0, 0)] * (tab.ndim - 2)
               + [(0, S_max - tab.shape[-2]), (0, 0)])
        return np.pad(tab, pad)

    pub_tab, del_tab = pad_slots(pub_tab), pad_slots(del_tab)
    rp_tab, rd_tab = pad_slots(rp_tab), pad_slots(rd_tab)

    # -- per-generation member arrays ------------------------------------
    N = nP * G
    Np = 1 << max(0, N - 1).bit_length()       # pow2 pad-and-mask bucket
    pr_m = np.tile(np.repeat(np.arange(nP), G), (nGen, 1))
    loc = np.tile(np.arange(G), nP)
    valid = np.zeros((nGen, Np), dtype=bool)
    i_glob = np.zeros((nGen, Np), dtype=np.int64)
    q_m = np.zeros((nGen, Np), dtype=np.int64)
    pub_ci = np.zeros((nGen, Np), dtype=np.int64)
    mem_id = np.zeros((nGen, Np), dtype=np.int64)
    for g in range(nGen):
        ii = g * G + loc                        # per-producer msg index
        ok = ii < M
        valid[g, :N] = ok
        i_glob[g, :N] = np.minimum(ii, M - 1)
        for pr in range(nP):
            ql = np.asarray(prod_queues[pr])
            sl = slice(pr * G, (pr + 1) * G)
            qs = ql[(pr + ii[sl]) % ql.size]
            q_m[g, sl] = qs
            pub_ci[g, sl] = [pub_idx_of[(pr, int(q))] for q in qs]
        mem_id[g, :N] = pr_m[g] * M + np.minimum(ii, M - 1)
    pr_mat = np.zeros((nGen, Np), dtype=np.int64)
    pr_mat[:, :N] = pr_m
    has_gate = valid & (i_glob >= W)
    # invalid pad members write confirm slot W (a scratch column past
    # the ring) so masked writes can never collide with live slots
    conf_slot = np.where(valid, i_glob % W, W)

    # static round-robin bases: per-generation queue/consumer/producer
    # arrival counts are order-independent, so the RR cursors are
    # precomputed instead of carried
    cnt_q = np.zeros((nGen, nq), dtype=np.int64)
    cnt_c = np.zeros((nGen, nC), dtype=np.int64)
    cq = np.zeros(nq, dtype=np.int64)
    cc = np.zeros(nC, dtype=np.int64)
    for g in range(nGen):
        cnt_q[g], cnt_c[g] = cq.copy(), cc.copy()
        counts = np.bincount(q_m[g][valid[g]], minlength=nq)
        for qi in range(nq):
            n, k = int(counts[qi]), int(kq[qi])
            for pp in range(n):
                cc[q_cons_tab[qi, (cq[qi] + pp) % k]] += 1
            cq[qi] += n
    # producer reply counts: pr receives exactly its own valid msgs;
    # padded with a scratch column for the dummy reply chain
    per_gen_p = np.stack([np.bincount(pr_mat[g][valid[g]], minlength=nP)
                          for g in range(nGen)])
    cnt_p = np.concatenate([np.zeros((1, nP), dtype=np.int64),
                            np.cumsum(per_gen_p, axis=0)[:-1]])
    cnt_p = np.concatenate(
        [cnt_p, np.zeros((nGen, 1), dtype=np.int64)], axis=1)

    # software-pipelined scan inputs: step g publishes generation g and
    # delivers generation g-1.  Feedback replies are not scheduled
    # here: each consumed message's reply waits in the scan carry's
    # pending-reply buffer until the publish and delivery clocks reach
    # it (``_reply_admit``).  After the nGen + 1 steps of the request
    # legs come ``drain`` steps that serve whatever replies are left.
    # A step serves at most Np replies per leg, all of them once no
    # request leg is left, so nGen steps drain a full buffer through
    # reply-publish, and each producer's reply-deliveries take at most
    # ceil(M / P) more: its reply n waits on the ack of reply n - P.
    drain = (nGen + -(-M // int(p.prefetch)) + 1) if feedback else 0
    nSteps = nGen + 1 + drain
    C = max(nP * M, Np) if feedback else 0

    def shift(a: np.ndarray, by: int) -> np.ndarray:
        out = np.zeros((nSteps,) + a.shape[1:], dtype=a.dtype)
        out[by:by + nGen] = a
        return out

    meta = dict(
        Np=Np, L=L, S_pub=S_pub, S_del=S_del, S_rp=S_rp, S_rd=S_rd,
        S_max=S_max, feedback=feedback, NR=NR, NCH=NCH, nq=nq, nC=nC,
        nP=nP, kq_max=kq_max, P=int(p.prefetch), B=int(p.ack_batch),
        W=W, G=G, nGen=nGen, nSteps=nSteps, pools=tuple(pools))
    xs = dict(
        pub_valid=shift(valid, 0), pub_pr=shift(pr_mat, 0),
        pub_ci=shift(pub_ci, 0), pub_has_gate=shift(has_gate, 0),
        pub_conf_slot=shift(np.where(valid, conf_slot, W), 0),
        del_valid=shift(valid, 1), del_q=shift(q_m, 1),
        del_cnt_q=shift(cnt_q, 1), del_cnt_c=shift(cnt_c, 1))
    xs["pub_conf_slot"][nGen:] = W      # drain steps hit the scratch slot
    if feedback:
        # a link on every path of a request leg that every path of a
        # reply leg crosses the other way, and the other request leg
        # never (PRS's tunnel: publishes in, reply-deliveries out), is
        # one FIFO chain; every other resource keeps one chain per
        # direction, as in work sharing (see _serve_leg)
        pipes = {rid_of[k] for k in res_keys if res_index[k].kind == "pipe"}
        pub_r, del_r = _crossed(pub_tab), _crossed(del_tab)
        merged = tuple(sorted(pipes & (
            ((_crossed(rd_tab, True) & _crossed(pub_tab, True)) - del_r)
            | ((_crossed(rp_tab, True) & _crossed(del_tab, True)) - pub_r))))
        # the ingress chains that replies share with publishes
        shared = tuple(int(chain_base[r]) + j
                       for r in sorted(_crossed(rp_tab) & pub_r)
                       for j in range(int(k_arr[r])))
        meta.update(M=M, C=C, drain=drain, merged=merged, shared=shared,
                    horizon=float(sim._slack))
        # the buffer row of each delivered message (producer * M +
        # index); invalid members write the dummy row C
        xs["del_mid"] = shift(np.where(valid, mem_id, C), 1)
    inv_arrays = dict(
        pub_tab=pub_tab, del_tab=del_tab, rp_tab=rp_tab, rd_tab=rd_tab,
        q_cons_tab=q_cons_tab, kq=kq, k_arr=k_arr, chain_base=chain_base,
        scal=np.array([arch.client_flush_s(),
                       arch.control_latency_s(),
                       sim._recv_latency(size),
                       sim._recv_latency(reply_size),
                       sim._proc_s]))
    sizes = dict(nP=nP, nC=nC, M=M, G=G, nGen=nGen, N=N, Np=Np, L=L,
                 n_jit=(4 if feedback else 2) * S_max + 1,
                 mem_id=mem_id, valid=valid)
    return WaveStatic(meta=meta, xs=xs, inv=inv_arrays, sizes=sizes)


@spans.traced("streamsim.draw_jitter")
def draw_jitter(sim: VectorizedStreamSim, ws: WaveStatic) -> dict:
    """Per-lane jitter draws for every (generation, slot, member), from
    the engine's per-seed streams.  One flat draw per lane in a fixed
    layout keeps each lane's realization independent of how many other
    lanes are stacked (lane-addition inertness by construction).
    Returned pre-shifted per pipeline leg, ready to merge into ``xs``;
    with feedback, the reply legs' draws by message row as well, which
    the program reads whole rather than step by step."""
    s, m = ws.sizes, ws.meta
    j = sim.p.jitter
    raw = np.zeros((s["nGen"], s["n_jit"], s["Np"], s["L"]))
    if j:
        for lane, rng in enumerate(sim._rngs):
            raw[..., lane] = rng.uniform(
                -j, j, size=(s["nGen"], s["n_jit"], s["Np"]))
    nSteps = m["nSteps"]

    def shift(a: np.ndarray, by: int) -> np.ndarray:
        out = np.zeros((nSteps,) + a.shape[1:])
        out[by:by + s["nGen"]] = a
        return out

    S = m["S_max"]
    jit = dict(pub_jit=shift(raw[:, :S], 0),
               del_jit=shift(raw[:, S:2 * S], 1),
               proc_jit=shift(raw[:, 2 * S], 1))
    if m["feedback"]:
        # a reply's draws follow its message, whichever step serves it:
        # rows by message (``producer * M + index``), as in the buffer
        g, mem = np.nonzero(s["valid"])
        rows = s["mem_id"][g, mem]
        for k, lo in (("rp_jit", 2 * S + 1), ("rd_jit", 3 * S + 1)):
            by_row = np.zeros((m["C"] + 1, S, s["L"]))
            by_row[rows] = raw[g, lo:lo + S, mem]
            jit[k] = by_row
    return jit


# ---------------------------------------------------------------------------
# Backend ops
# ---------------------------------------------------------------------------


class _NumpyOps:
    """Reference backend: the same step function run as a plain Python
    loop — the step-for-step oracle for the device program."""

    xp = np

    @staticmethod
    def scope(name: str) -> Any:
        return contextlib.nullcontext()

    @staticmethod
    def lexsort(keys: tuple) -> np.ndarray:
        return np.lexsort(keys)

    @staticmethod
    def cumsum(x: np.ndarray) -> np.ndarray:
        return np.cumsum(x, axis=0)

    @staticmethod
    def cummax(x: np.ndarray) -> np.ndarray:
        return np.maximum.accumulate(x, axis=0)

    @staticmethod
    def seg_cummax(x: np.ndarray, start: np.ndarray) -> np.ndarray:
        out = x.copy()
        for i in range(1, x.shape[0]):
            if not start[i]:
                out[i] = np.maximum(out[i - 1], x[i])
        return out

    @staticmethod
    def at_set(arr, idx, vals):
        out = arr.copy()
        out[idx] = vals
        return out

    @staticmethod
    def at_max(arr, idx, vals):
        out = arr.copy()
        np.maximum.at(out, idx, vals)
        return out

    @staticmethod
    def scan(step: Callable, carry: Any, xs: dict, n: int
             ) -> tuple[Any, dict]:
        ys_all: dict = {}
        for g in range(n):
            carry, ys = step(carry, {k: v[g] for k, v in xs.items()})
            for k, v in ys.items():
                ys_all.setdefault(k, []).append(v)
        return carry, {k: np.stack(v) for k, v in ys_all.items()}


def _jax_ops() -> Any:
    import jax
    import jax.numpy as jnp
    from jax import lax

    class _JaxOps:
        xp = jnp

        # names the phase's ops in the HLO metadata, so a profile can
        # attribute device time to it; no effect on the arithmetic
        @staticmethod
        def scope(name: str):
            return jax.named_scope(name)

        @staticmethod
        def lexsort(keys: tuple):
            return jnp.lexsort(keys)

        # associative scans, not jnp.cumsum / lax.cummax: those lower to
        # a reduce-window whose 64-bit emulation the TPU compiler takes
        # a minute to compile at 128 rows
        @staticmethod
        def cumsum(x):
            return lax.associative_scan(jnp.add, x)

        @staticmethod
        def cummax(x):
            return lax.associative_scan(jnp.maximum, x)

        @staticmethod
        def seg_cummax(x, start):
            s = start.reshape(start.shape + (1,) * (x.ndim - 1))

            def comb(l, r):
                xl, sl = l
                xr, sr = r
                return (jnp.where(sr, xr, jnp.maximum(xl, xr)), sl | sr)
            y, _ = lax.associative_scan(
                comb, (x, jnp.broadcast_to(s, x.shape)))
            return y

        @staticmethod
        def at_set(arr, idx, vals):
            return arr.at[idx].set(vals)

        @staticmethod
        def at_max(arr, idx, vals):
            return arr.at[idx].max(vals)

        @staticmethod
        def scan(step, carry, xs, n):
            return lax.scan(lambda c, x: step(c, x), carry, xs, length=n)

    return _JaxOps


# ---------------------------------------------------------------------------
# The wave program (backend-generic)
# ---------------------------------------------------------------------------


def _serve_leg(ops: Any, free: Array, a: Array, hold: Array,
               kind: Array, rid: Array, lat: Array, valid: Array,
               side: Array, meta: dict, chain_base: Array,
               k_arr: Array) -> tuple:
    """FIFO-serve one aligned path slot for all members: segmented
    closed-form scans over (resource chain)-grouped members, with
    earliest-free pool server interleaving and cross-generation carries.

    ``free``: ``(2*NCH+1, L)`` per-chain busy-until carries (last row
    is the dummy chain absorbing latency-only/invalid members).  Each
    resource has TWO chain copies, one per traffic *direction*
    (``side`` 0: ingress-bound publish/reply-publish, 1: egress-bound
    delivery/reply-delivery).  Same-direction flows genuinely contend
    at the saturated gateway pipes and have comparable clock lags, so
    they share a FIFO chain; cross-direction sharing only happens at
    many-server fabric internals whose real contention is negligible —
    and a shared busy-until carry there would *invent* contention,
    because it cannot represent the idle gap between the two
    directions' disjoint usage windows.  Returns ``(free', t_out)``."""
    xp = ops.xp
    NCH = meta["NCH"]
    dummy = 2 * NCH
    idx = xp.arange(a.shape[0])      # combined (multi-leg) member axis
    is_res = (kind > 0) & valid
    pilot = xp.where(is_res, a[:, 0], _INF)
    # latency-only / invalid members get unique singleton chains past
    # the resource id space so the segmented scan leaves them alone
    rid_key = xp.where(is_res, rid + side * meta["NR"],
                       2 * meta["NR"] + idx)
    # pool-carry ordering: vectorized serves each pool with its carries
    # sorted by the pilot lane ascending (earliest-free server first)
    for (b, kk) in meta["pools"]:
        for off in (0, NCH):
            sub = free[b + off:b + off + kk]
            order = ops.lexsort((xp.arange(kk), sub[:, 0]))
            free = ops.at_set(free, xp.arange(b + off, b + off + kk),
                              sub[order])
    # stage 1: group by (resource, direction), pilot-arrival order
    # within the group
    o1 = ops.lexsort((idx, pilot, rid_key))
    rk1, a1 = rid_key[o1], a[o1]
    start1 = xp.concatenate([xp.ones(1, dtype=bool), rk1[1:] != rk1[:-1]])
    segfirst = ops.cummax(xp.where(start1, idx, -1))
    pos = idx - segfirst
    k1 = k_arr[xp.clip(rid[o1], 0, meta["NR"] - 1)]
    server = xp.where(is_res[o1], pos % k1, 0)
    chain = xp.where(is_res[o1],
                     chain_base[xp.clip(rid[o1], 0, meta["NR"] - 1)]
                     + server + side[o1] * NCH, dummy)
    chain_key = xp.where(is_res[o1], chain, dummy + 1 + idx)
    # stage 2: make each chain contiguous, preserving pilot order
    o2 = ops.lexsort((idx, chain_key))
    a2, chain2, chkey2 = a1[o2], chain[o2], chain_key[o2]
    h2 = hold[o1][o2]
    res2 = is_res[o1][o2]
    start2 = xp.concatenate([xp.ones(1, dtype=bool),
                             chkey2[1:] != chkey2[:-1]])
    carry = free[chain2]
    a_eff = xp.where(res2[:, None], xp.maximum(a2, carry), a2)
    # segmented FIFO closed form: e = H + segcummax(a - (H - h))
    c = ops.cumsum(h2)
    basefill = ops.cummax(xp.where(start2[:, None], c - h2, -_INF))
    Hs = c - basefill
    e2 = Hs + ops.seg_cummax(a_eff - (Hs - h2), start2)
    free = ops.at_max(free, xp.where(res2, chain2, dummy), e2)
    perm = o1[o2]
    t_out = ops.at_set(xp.zeros_like(a), perm, e2 + lat[perm][:, None])
    return free, t_out


def _transit(ops: Any, free: Array, t: Array, slots: Array, jit: Array,
             valid: Array, side: Array, meta: dict, chain_base: Array,
             k_arr: Array) -> tuple:
    """Walk members through an aligned path: ``slots`` is
    ``(S, Np, 4)`` rows of (kind, rid, hold_base, lat).  The slots run
    as a scan, so the device program holds one copy of the serve."""
    xp = ops.xp

    def slot(carry: tuple, x: dict) -> tuple:
        free, t = carry
        row = x["row"]
        kind = row[:, 0].astype(xp.int64)
        rid = row[:, 1].astype(xp.int64)
        hold = xp.where((kind > 0) & valid,
                        row[:, 2], 0.0)[:, None] * (1.0 + x["jit"])
        sd = side
        for r in meta.get("merged", ()):
            sd = xp.where(rid == r, 0, sd)
        return _serve_leg(ops, free, t, hold, kind, rid, row[:, 3],
                          valid, sd, meta, chain_base, k_arr), {}

    (free, t), _ = ops.scan(slot, (free, t), {"row": slots, "jit": jit},
                            slots.shape[0])
    return free, t


def _next_boundary(ops: Any, boundary: Array, start: Array,
                   Np: int) -> Array:
    """Index of the nearest boundary at or after each position within
    its segment (exists: segment ends are always boundaries)."""
    xp = ops.xp
    idx = xp.arange(Np)
    end = xp.concatenate([start[1:], xp.ones(1, dtype=bool)])
    r = xp.where(boundary, idx, _IBIG)[::-1]
    nb_rev = -ops.seg_cummax(-r, end[::-1])
    return nb_rev[::-1]


def _pump_assign(ops: Any, ring: Array, t_ready: Array, gid: Array,
                 idx_on: Array, valid: Array, meta: dict) -> Array:
    """The pump window assignment: gate each message on the prefetch
    ring of its assigned consumer and clamp the depart.
    ``gid``: consumer id per member; ``idx_on``: the message's index in
    its consumer's total delivery order."""
    xp = ops.xp
    P = meta["P"]
    gate = ring[gid, idx_on % P]
    gate = xp.where((idx_on >= P)[:, None] & valid[:, None], gate, 0.0)
    return xp.maximum(t_ready, gate)


def _seg_pos(ops: Any, key_sorted: Array, Np: int) -> tuple:
    """(segment-start flags, position within segment) for a sorted
    integer key array."""
    xp = ops.xp
    idx = xp.arange(Np)
    start = xp.concatenate([xp.ones(1, dtype=bool),
                            key_sorted[1:] != key_sorted[:-1]])
    pos = idx - ops.cummax(xp.where(start, idx, -1))
    return start, pos


def _first_eligible(ops: Any, arrival: Array, eligible: Array,
                    n: int) -> tuple:
    """Rows of the ``n`` eligible entries with the earliest pilot-lane
    arrival (ties by row), and which of them are eligible at all."""
    xp = ops.xp
    rows = xp.arange(arrival.shape[0])
    order = ops.lexsort((rows, xp.where(eligible, arrival[:, 0], _INF),
                         (~eligible).astype(xp.int64)))
    sel = order[:n]
    return sel, eligible[sel]


def _latest(xp: Any, t: Array, valid: Array) -> Array:
    """Latest valid pilot-lane time, ``+inf`` where none is valid."""
    return xp.where(valid.any(), xp.max(xp.where(valid, t[:, 0], -_INF)),
                    _INF)


def _reply_admit(ops: Any, meta: dict, rep: dict, hz_rp: Array,
                 win_rp: tuple, hz_rd: Array) -> tuple:
    """Take this step's replies out of the pending-reply buffer.

    A reply is published once its consumer's processing end (``rp_t``)
    is no later than ``hz_rp``, or lies inside ``win_rp``; it is
    delivered once its departure from the producer's reply queue is no
    later than ``hz_rd``.  Up to ``Np`` of each go, earliest first.
    The departure is the reply's arrival at the queue, gated, past the
    prefetch window, on the producer's ack of its reply ``n - P``: a
    reply whose gate is not known yet (that ack not yet taken) waits."""
    xp = ops.xp
    Np, P, M, C, nP = meta["Np"], meta["P"], meta["M"], meta["C"], meta["nP"]
    row_pr = xp.minimum(xp.arange(C + 1) // M, nP)
    a = rep["rp_t"][:, 0]
    rp_sel, v_rp = _first_eligible(
        ops, rep["rp_t"], rep["rp_v"] & ((a <= hz_rp) | (
            (a >= win_rp[0]) & (a <= win_rp[1]))), Np)
    n_on = rep["rd_i"]
    slot = n_on % P
    gated = n_on >= P
    dep = xp.where(gated[:, None],
                   xp.maximum(rep["rd_t"], rep["prep"][row_pr, slot]),
                   rep["rd_t"])
    known = ~gated | (rep["ptag"][row_pr, slot] == n_on - P)
    rd_sel, v_rd = _first_eligible(
        ops, dep, rep["rd_v"] & known & (dep[:, 0] <= hz_rd), Np)
    dummy = C
    rp_rows = xp.where(v_rp, rp_sel, dummy)
    rd_rows = xp.where(v_rd, rd_sel, dummy)
    rep = dict(rep, rp_v=ops.at_set(rep["rp_v"], rp_rows, False),
               rd_v=ops.at_set(rep["rd_v"], rd_rows, False))
    rp = dict(row=rp_rows, valid=v_rp, t=rep["rp_t"][rp_sel],
              cons=rep["rp_c"][rp_sel], pr=row_pr[rp_rows])
    rd = dict(row=rd_rows, valid=v_rd, t=dep[rd_sel],
              n_on=n_on[rd_sel], pr=row_pr[rd_rows])
    return rep, rp, rd


def _cat(xp: Any, blocks: list, lo: int = 0,
         hi: Optional[int] = None) -> tuple:
    """The combined member axis of ``blocks`` ``(arrival, valid, slots,
    jitter, side)``, over their aligned slots ``[lo, hi)``."""
    return (xp.concatenate([b[0] for b in blocks], axis=0),
            xp.concatenate([b[2][lo:hi] for b in blocks], axis=1),
            xp.concatenate([b[3][lo:hi] for b in blocks], axis=1),
            xp.concatenate([b[1] for b in blocks], axis=0),
            xp.concatenate([xp.full(b[1].shape[0], b[4], dtype=xp.int64)
                            for b in blocks]))


def _wave_step(ops: Any, meta: dict, inv: dict, carry: dict,
               x: dict) -> tuple[dict, dict]:
    """One *pipelined* scan step.

    Step ``g`` publishes generation ``g`` and delivers ``g-1``; with
    feedback it also reply-publishes and reply-delivers the replies
    that :func:`_reply_admit` takes from the pending-reply buffer.
    Every leg's arrivals are known at step entry (the publish ->
    delivery hand-off rides the ``t_enq`` carry, replies the buffer),
    so all transits run as ONE combined serve over a concatenated
    member axis: shared resources see the competing flows in true
    arrival order, not phase-convoy order — the property that keeps
    throughput and RTT inside the parity bands.  Scan length is
    ``nGen + 1`` plus, with feedback, the reply drain steps; shifted
    validity masks drain the tail."""
    xp = ops.xp
    Np, L, P, B = meta["Np"], meta["L"], meta["P"], meta["B"]
    nC, nP, fb = meta["nC"], meta["nP"], meta["feedback"]
    flush, ctrl, recv_req, recv_rep, proc_s = (inv["scal"][i]
                                               for i in range(5))
    idx = xp.arange(Np)

    # ---- per-leg arrivals (all independent at step entry) -------------
    # publish(g): confirm-window admission gates + client flush
    with ops.scope("publish_gate"):
        v_pub, pr = x["pub_valid"], x["pub_pr"]
        gate = carry["conf"][pr, x["pub_conf_slot"]]
        gate = xp.where(x["pub_has_gate"][:, None], gate, 0.0)
        pub_start = xp.where(v_pub[:, None], gate + flush, _INF)

    # delivery(g-1): pump window assignment — per-queue arrival-order
    # round robin with prefetch-ring gates
    with ops.scope("pump_assign"):
        v_del, q = x["del_valid"], x["del_q"]
        t_enq_prev = carry["t_enq"]
        oq = ops.lexsort((idx, xp.where(v_del, t_enq_prev[:, 0], _INF),
                          xp.where(v_del, q, meta["nq"])))
        q_s = q[oq]
        _, posq = _seg_pos(ops, xp.where(v_del[oq], q_s, meta["nq"]), Np)
        kqv = inv["kq"][xp.clip(q_s, 0, meta["nq"] - 1)]
        slot_c = (x["del_cnt_q"][xp.clip(q_s, 0, meta["nq"] - 1)]
                  + posq) % kqv
        cons_s = inv["q_cons_tab"][xp.clip(q_s, 0, meta["nq"] - 1), slot_c]
        idx_on_c = x["del_cnt_c"][cons_s] + posq // kqv
        depart_s = _pump_assign(ops, carry["ack"], t_enq_prev[oq], cons_s,
                                idx_on_c, v_del[oq], meta)
        cons = ops.at_set(xp.zeros(Np, dtype=cons_s.dtype), oq, cons_s)
        idxc = ops.at_set(xp.zeros(Np, dtype=idx_on_c.dtype), oq, idx_on_c)
        slotc = ops.at_set(xp.zeros(Np, dtype=slot_c.dtype), oq, slot_c)
        depart = ops.at_set(xp.zeros_like(t_enq_prev), oq, depart_s)
        depart = xp.where(v_del[:, None], depart, _INF)

    blocks = [
        (pub_start, v_pub,
         xp.swapaxes(inv["pub_tab"][x["pub_ci"]], 0, 1), x["pub_jit"], 0),
        (depart, v_del,
         xp.swapaxes(inv["del_tab"][q, slotc], 0, 1), x["del_jit"], 1),
    ]
    # ---- combined transit: all legs, one serve per aligned slot -------
    # (direction per block: publish/reply-publish are ingress-bound (0),
    # delivery/reply-delivery egress-bound (1))
    if not fb:
        with ops.scope("transit"):
            free, t_c = _transit(ops, carry["free"], *_cat(xp, blocks),
                                 meta, inv["chain_base"], inv["k_arr"])
    else:
        # the request legs take their first hop (the producer's NIC, the
        # queue's broker) alone; replies then join the step whose
        # request legs have reached them past it: the publishes for
        # reply-publish, the deliveries for reply-delivery.  A step
        # without request legs (the drain) takes any.
        with ops.scope("transit"):
            free, t_req = _transit(ops, carry["free"],
                                   *_cat(xp, blocks, 0, 1), meta,
                                   inv["chain_base"], inv["k_arr"])
        with ops.scope("reply_admit"):
            # the clocks the request legs reached past their first hop;
            # replies trail the publishes by the cohort engines' own
            # horizon, as their batches serve up to it ahead
            hz_pub = _latest(xp, t_req[:Np], v_pub) - meta["horizon"]
            hz_del = _latest(xp, t_req[Np:], v_del)
            # a reply that finds the ingress chains it shares with the
            # publishes idle cannot overtake a publish served before
            # it: it goes once the deliveries' clock reaches it
            idle = (xp.max(free[xp.asarray(meta["shared"]), 0])
                    if meta["shared"] else -_INF)
            rep, rp, rd = _reply_admit(ops, meta, carry["reply"], hz_pub,
                                       (idle, hz_del), hz_del)
            replies = [
                (rp["t"], rp["valid"],
                 xp.swapaxes(inv["rp_tab"][xp.clip(rp["cons"], 0, nC - 1),
                                           xp.clip(rp["pr"], 0, nP - 1)],
                             0, 1),
                 xp.swapaxes(inv["rp_jit"][rp["row"]], 0, 1), 0),
                (rd["t"], rd["valid"],
                 xp.swapaxes(inv["rd_tab"][xp.clip(rd["pr"], 0, nP - 1)],
                             0, 1),
                 xp.swapaxes(inv["rd_jit"][rd["row"]], 0, 1), 1)]
        with ops.scope("transit"):
            free, t_rep = _transit(ops, free, *_cat(xp, replies, 0, 1),
                                   meta, inv["chain_base"], inv["k_arr"])
            t0 = (t_req[:Np], t_req[Np:], t_rep[:Np], t_rep[Np:])
            blocks = [(t,) + b[1:] for t, b in zip(t0, blocks + replies)]
            free, t_c = _transit(ops, free, *_cat(xp, blocks, 1), meta,
                                 inv["chain_base"], inv["k_arr"])
    t_enq = t_c[:Np]
    t_land = t_c[Np:2 * Np]

    # ---- publish(g) epilogue: confirms feed the admission ring --------
    with ops.scope("publish_gate"):
        confirms = t_enq + ctrl
        conf = ops.at_set(carry["conf"], (pr, x["pub_conf_slot"]), confirms)

    # ---- delivery(g-1) epilogue: consumer processing + batched acks ---
    with ops.scope("consume_ack"):
        a = t_land + recv_req
        h = (xp.where(v_del, proc_s, 0.0)[:, None] * (1.0 + x["proc_jit"]))
        ch = xp.where(v_del, cons, nC)
        oc = ops.lexsort((idx, xp.where(v_del, a[:, 0], _INF), ch))
        ch_s = ch[oc]
        start_c, posc = _seg_pos(ops, ch_s, Np)
        carry_pf = carry["proc"][ch_s]
        a_eff = xp.where((ch_s < nC)[:, None],
                         xp.maximum(a[oc], carry_pf), a[oc])
        h_s = h[oc]
        c = ops.cumsum(h_s)
        basefill = ops.cummax(xp.where(start_c[:, None], c - h_s, -_INF))
        Hs = c - basefill
        seen_s = Hs + ops.seg_cummax(a_eff - (Hs - h_s), start_c)
        proc = ops.at_max(carry["proc"], ch_s, seen_s)
        seen = ops.at_set(xp.zeros_like(a), oc, seen_s)
        seen = xp.where(v_del[:, None], seen, _INF)
        # acks: batch every B in processing order, force-flush at
        # generation end; invalid members route to the dummy ring row
        # nC (valid slots within a generation are distinct: load < P)
        boundary = ((((posc + 1) % B) == 0)
                    | xp.concatenate([start_c[1:], xp.ones(1, dtype=bool)]))
        nb = _next_boundary(ops, boundary | (ch_s >= nC), start_c, Np)
        ack = ops.at_set(carry["ack"], (ch_s, idxc[oc] % P),
                         seen_s[nb] + ctrl)

    ys = dict(pub_start=pub_start, confirms=confirms, depart=depart,
              seen=seen)
    carry = dict(carry, free=free, conf=conf, proc=proc, ack=ack,
                 t_enq=t_enq)
    if not fb:
        return carry, ys

    # ---- reply-publish epilogue: number each reply in its producer's
    #      reply queue, in arrival order; it waits there for delivery --
    with ops.scope("reply_pump"):
        t_renq = t_c[2 * Np:3 * Np]
        v_rp = rp["valid"]
        pch = xp.where(v_rp, rp["pr"], nP)
        opr = ops.lexsort((idx, xp.where(v_rp, t_renq[:, 0], _INF), pch))
        pr_s = pch[opr]
        _, posp = _seg_pos(ops, pr_s, Np)
        n_on_s = rep["cnt"][pr_s] + posp
        n_on = ops.at_set(xp.zeros(Np, dtype=n_on_s.dtype), opr, n_on_s)
        rep = dict(
            rep, cnt=ops.at_max(rep["cnt"], pr_s, n_on_s + 1),
            rd_t=ops.at_set(rep["rd_t"], rp["row"], t_renq),
            rd_i=ops.at_set(rep["rd_i"], rp["row"], n_on),
            rd_v=ops.at_set(rep["rd_v"], rp["row"], v_rp))

    # ---- reply-delivery epilogue: receive times + producer ack batching
    with ops.scope("reply_ack"):
        v_rd = rd["valid"]
        t_seen = t_c[3 * Np:] + recv_rep
        pch_d = xp.where(v_rd, rd["pr"], nP)
        opd = ops.lexsort((idx, xp.where(v_rd, t_seen[:, 0], _INF),
                           pch_d))
        pd_s = pch_d[opd]
        start_p, posd = _seg_pos(ops, pd_s, Np)
        boundary = ((((posd + 1) % B) == 0)
                    | xp.concatenate([start_p[1:], xp.ones(1, dtype=bool)]))
        nb = _next_boundary(ops, boundary | (pd_s >= nP), start_p, Np)
        ring = (pd_s, rd["n_on"][opd] % P)
        rep = dict(
            rep, back=ops.at_set(rep["back"], rd["row"], t_seen),
            prep=ops.at_set(rep["prep"], ring, t_seen[opd][nb] + ctrl),
            ptag=ops.at_set(rep["ptag"], ring, rd["n_on"][opd]))

    # ---- this step's consumed messages join the buffer ----------------
    with ops.scope("reply_admit"):
        rows = x["del_mid"]
        rep = dict(rep, rp_t=ops.at_set(rep["rp_t"], rows, seen),
                   rp_c=ops.at_set(rep["rp_c"], rows, cons),
                   rp_v=ops.at_set(rep["rp_v"], rows, v_del))
        rep["peak"] = xp.maximum(
            rep["peak"], (rep["rp_v"].sum() + rep["rd_v"].sum()).astype(
                rep["peak"].dtype))
    return dict(carry, reply=rep), ys


def _init_carry(xp: Any, meta: dict) -> dict:
    # trailing dummy rows/slots absorb the masked writes of invalid
    # pad members: conf slot W, ack row nC, proc row nC, and with
    # feedback buffer row C and producer row nP
    L, Np = meta["L"], meta["Np"]
    carry = dict(
        free=xp.zeros((2 * meta["NCH"] + 1, L)),
        conf=xp.zeros((meta["nP"], meta["W"] + 1, L)),
        ack=xp.zeros((meta["nC"] + 1, meta["P"], L)),
        proc=xp.zeros((meta["nC"] + 1, L)),
        t_enq=xp.zeros((Np, L)))
    if meta["feedback"]:
        C, nP, P = meta["C"], meta["nP"], meta["P"]
        carry["reply"] = dict(
            # awaiting reply-publish: consumer's processing end, consumer
            rp_t=xp.zeros((C + 1, L)), rp_c=xp.zeros(C + 1, dtype=xp.int64),
            rp_v=xp.zeros(C + 1, dtype=bool),
            # awaiting reply-delivery: arrival at the producer's reply
            # queue, its number in that queue
            rd_t=xp.zeros((C + 1, L)), rd_i=xp.zeros(C + 1, dtype=xp.int64),
            rd_v=xp.zeros(C + 1, dtype=bool),
            # reply receive times (+inf: not received)
            back=xp.full((C + 1, L), _INF),
            # per producer: replies numbered so far, the ack ring and
            # the reply number whose ack each slot holds
            cnt=xp.zeros(nP + 1, dtype=xp.int64),
            prep=xp.zeros((nP + 1, P, L)),
            ptag=xp.full((nP + 1, P), -1, dtype=xp.int64),
            peak=xp.zeros((), dtype=xp.int64))
    return carry


def _finals(meta: dict, carry: dict) -> dict:
    """Outputs taken from the carry at the end of the scan: each
    message's reply receive time, by message row, and the largest
    pending-reply count."""
    if not meta["feedback"]:
        return {}
    rep = carry["reply"]
    return dict(back=rep["back"][:meta["nP"] * meta["M"]],
                reply_peak=rep["peak"])


def run_wave_trace(ws: WaveStatic, jitter: dict,
                   backend: str = "jax") -> dict:
    """Run the wave program, returning the full per-step trace
    ``{pub_start, confirms, depart, seen}`` with leading axis
    ``nSteps`` (with feedback, also ``back`` and ``reply_peak``, see
    :func:`_finals`) — the step-for-step comparison surface for the
    property tests.  ``backend="numpy"`` runs the same step as a Python
    loop."""
    meta = dict(ws.meta)
    if backend == "numpy":
        ops: Any = _NumpyOps
        xs, inv = _split_inputs(ws.xs, ws.inv, jitter)
        carry, ys = ops.scan(
            lambda c, x: _wave_step(ops, meta, inv, c, x),
            _init_carry(np, meta), xs, meta["nSteps"])
        return dict(ys, **_finals(meta, carry))
    return dict(_run_jax(ws, jitter))


#: jitter draws read step by step; the rest are read whole
_STEP_JIT = ("pub_jit", "del_jit", "proc_jit")


def _split_inputs(xs: dict, inv: dict, jitter: dict) -> tuple:
    return (dict(xs, **{k: v for k, v in jitter.items() if k in _STEP_JIT}),
            dict(inv, **{k: v for k, v in jitter.items()
                         if k not in _STEP_JIT}))


@functools.lru_cache(maxsize=64)
def _compiled_program(sig: tuple, meta_items: tuple, n_cells: bool
                      ) -> Callable:
    """Jit (once per static signature / shape bucket) the whole-run
    program; ``n_cells`` selects the vmap-over-cells variant.  Call it
    through :func:`_run_program` (scoped x64)."""
    import jax

    spans.count("streamsim.program_builds", 1)
    ops = _jax_ops()
    meta = dict(meta_items)
    meta["pools"] = tuple(meta["pools"])

    def program(xs: dict, inv: dict, jitter: dict) -> dict:
        xs, inv = _split_inputs(xs, inv, jitter)
        carry, ys = ops.scan(
            lambda c, x: _wave_step(ops, meta, inv, c, x),
            _init_carry(ops.xp, meta), xs, meta["nSteps"])
        return dict(ys, **_finals(meta, carry))

    return jax.jit(jax.vmap(program) if n_cells else program)


class Outputs(Mapping):
    """The outputs of one dispatched program, read as host arrays.

    The first read waits on the program (``streamsim.wait``); each
    output is copied to the host on its first read (``streamsim.fetch``,
    counted in ``streamsim.d2h_bytes``), so an output nobody reads is
    never copied."""

    def __init__(self, dev: dict) -> None:
        self._dev = dev
        self._host: dict = {}

    def prefetch(self, keys: tuple) -> None:
        """Start copying ``keys`` to the host as soon as the program
        ends, so their reads find them there."""
        for k in keys:
            self._dev[k].copy_to_host_async()

    def __getitem__(self, key: str) -> np.ndarray:
        a = self._host.get(key)
        if a is None:
            dev = self._dev[key]
            if not self._host:
                import jax
                with spans.span("streamsim.wait"):
                    jax.block_until_ready(self._dev)
            with spans.span("streamsim.fetch"):
                a = self._host[key] = np.asarray(dev)
            spans.count("streamsim.d2h_bytes", a.nbytes)
        return a

    def __iter__(self) -> Iterator[str]:
        return iter(self._dev)

    def __len__(self) -> int:
        return len(self._dev)


class _Cell(Mapping):
    """Cell ``c`` of a vmapped program's outputs, sliced as it is read."""

    def __init__(self, ys: Mapping, c: int) -> None:
        self._ys, self._c = ys, c

    def __getitem__(self, key: str) -> np.ndarray:
        return self._ys[key][self._c]

    def __iter__(self) -> Iterator[str]:
        return iter(self._ys)

    def __len__(self) -> int:
        return len(self._ys)


def _run_program(fn: Callable, *args: Any) -> Outputs:
    """Enqueue a compiled program under scoped x64 and return its
    :class:`Outputs` without waiting on it.

    Span ``dispatch`` is the call up to its return: inputs handed to
    the device, the program enqueued.  Reading the outputs waits and
    copies them back (``wait``, ``fetch``)."""
    from repro.compat import scoped_x64
    with spans.span("streamsim.program"):
        with scoped_x64(), spans.span("streamsim.dispatch"):
            out = fn(*args)
    if spans.on():
        spans.count("streamsim.programs", 1)
        spans.count("streamsim.h2d_bytes",
                    sum(a.nbytes for d in args for a in d.values()))
    return Outputs(out)


def _count_cells(wss: list, n_padded: int) -> None:
    """Counters of one program run over the cells ``wss``, ``n_padded``
    of them after the pow2 pad: member-steps that carry a message, and
    all the program computes (pow2 member and cell pads, drain steps)."""
    if not spans.on():
        return
    m = wss[0].meta
    spans.count("streamsim.cells", len(wss))
    spans.count("streamsim.member_steps",
                m["L"] * sum(int(ws.sizes["valid"].sum()) for ws in wss))
    spans.count("streamsim.padded_member_steps",
                m["Np"] * m["L"] * m["nSteps"] * n_padded)
    spans.count("streamsim.scan_steps", m["nSteps"])
    if m["feedback"]:
        spans.count("streamsim.drain_steps", m["drain"])
        spans.count("streamsim.reply_capacity", m["C"] * len(wss))


def _run_jax(ws: WaveStatic, jitter: dict) -> Outputs:
    fn = _compiled_program(ws.signature(), _meta_key(ws.meta), False)
    _count_cells([ws], 1)
    return _run_program(fn, ws.xs, ws.inv, jitter)


def _meta_key(meta: dict) -> tuple:
    return tuple(sorted(meta.items()))


# ---------------------------------------------------------------------------
# Result assembly + engine/campaign entry points
# ---------------------------------------------------------------------------


def _read_keys(meta: dict) -> tuple:
    """The outputs :func:`_assemble` reads."""
    return ("seen", "pub_start") + (("back",) if meta["feedback"] else ())


@spans.traced("streamsim.assemble")
def _assemble(sim: VectorizedStreamSim, ws: WaveStatic,
              ys: Mapping) -> list:
    """Per-lane RunResults from the generation trace, through the
    engine's own ``_result`` contract (attribution, counters, sort).
    Reading ``ys`` (an :class:`Outputs`) waits on the program and
    copies back the outputs read here (:func:`_read_keys`)."""
    s = ws.sizes
    nP, M, L, nGen = s["nP"], s["M"], s["L"], s["nGen"]
    mem = s["mem_id"].ravel()
    valid = s["valid"].ravel()
    lanes = () if L == 1 else (L,)
    consume_t = np.full((nP * M,) + lanes, np.nan)
    pub = np.zeros((nP * M,) + lanes)
    # de-stagger the pipelined trace: step g carries publish(g) and
    # delivery(g-1)
    seen = ys["seen"][1:nGen + 1].reshape(-1, L)[valid]
    consume_t[mem[valid]] = (seen if lanes else seen[:, 0])
    ps = ys["pub_start"][:nGen].reshape(-1, L)[valid]
    pub[mem[valid]] = (ps if lanes else ps[:, 0])
    rtts = None
    if ws.meta["feedback"]:
        # by message row; a reply never received reads +inf
        back = ys["back"]
        rtts = (back if lanes else back[:, 0]) - pub
        if spans.on():
            spans.count("streamsim.reply_backlog_peak",
                        int(ys["reply_peak"]))
    sim.n_events = int(valid.sum()) * max(
        1, ws.meta["S_pub"] + ws.meta["S_del"]
        + ws.meta["S_rp"] + ws.meta["S_rd"])
    results = []
    for lane, seed in enumerate(sim.stack_seeds):
        lane_spec = dataclasses.replace(
            sim.spec, params=dataclasses.replace(sim.p, seed=seed))
        sel = (slice(None),) if L == 1 else (slice(None), lane)
        r = sim._result(lane_spec, consume_t[sel],
                        rtts[sel] if rtts is not None else None,
                        pub[sel], lane=lane)
        results.append(dataclasses.replace(r, path="jax-device"))
    return results


def run_wave_results(sim: VectorizedStreamSim) -> list:
    """Whole-run device execution for one (possibly lane-stacked)
    engine instance; one RunResult per stacked seed-lane."""
    return run_wave_cells([sim])[0]


def _dispatch(cells: list, sig: tuple) -> Mapping:
    """Draw the jitter of ``cells`` (``(sim, WaveStatic)`` pairs of one
    signature) and enqueue their program, vmapped over the cells when
    there are several: the cell axis pow2-padded by replicating cell 0
    (pads are dropped; inertness is property-tested).  Starts copying
    back what assembly reads."""
    sim0, ws0 = cells[0]
    if len(cells) == 1:
        ys = _run_jax(ws0, draw_jitter(sim0, ws0))
    else:
        Cp = 1 << (len(cells) - 1).bit_length()
        padded = cells + cells[:1] * (Cp - len(cells))
        xs = {k: np.stack([ws.xs[k] for _, ws in padded]) for k in ws0.xs}
        inv = {k: np.stack([ws.inv[k] for _, ws in padded])
               for k in ws0.inv}
        draws = [draw_jitter(sim, ws) for sim, ws in padded]
        jit = {k: np.stack([d[k] for d in draws]) for k in draws[0]}
        _count_cells([ws for _, ws in cells], Cp)
        ys = _run_program(_compiled_program(sig, _meta_key(ws0.meta), True),
                          xs, inv, jit)
    if isinstance(ys, Outputs):  # a wrapped _run_program may return host arrays
        ys.prefetch(_read_keys(ws0.meta))
    return ys


def run_wave_cells(sims: Iterable) -> list:
    """vmap-over-cells driver: batch structurally identical cells
    (same :meth:`WaveStatic.signature`) into one device program.
    Returns, per sim, the per-lane RunResult list, in input order.

    The host runs one program ahead of the device.  The first sim is
    built and its program enqueued before the next sim is taken from
    ``sims``, so the later sims' construction (lazy in ``run_many``)
    and static builds run while the device computes; the first cell
    therefore runs as a program of its own even where a later cell
    shares its signature.  The rest are grouped by signature, and each
    group's program is enqueued before the host waits on the one
    before and assembles it, so draws, dispatch, copies and assembly
    overlap a program already queued.  At most two programs' buffers
    are alive on the device at once."""
    import jax.numpy as jnp  # noqa: F401  (jax required here)
    built: list = []
    out: dict = {}
    pending: list = []      # (idxs, outputs) enqueued, not yet assembled

    def build(sim: VectorizedStreamSim) -> tuple:
        """Build ``sim`` as cell ``len(built)``; returns its signature."""
        spans.count("streamsim.builds_ahead", int(bool(pending)))
        ws = build_static(sim)
        built.append((sim, ws))
        return ws.signature()

    def assemble(idxs: list, ys: Mapping) -> None:
        for c, i in enumerate(idxs):
            sim, ws = built[i]
            out[i] = _assemble(sim, ws, ys if len(idxs) == 1
                               else _Cell(ys, c))

    def dispatch(sig: tuple, idxs: list) -> None:
        pending.append((idxs, _dispatch([built[i] for i in idxs], sig)))
        spans.count("streamsim.programs_ahead", len(pending) - 1)
        if len(pending) > 1:
            assemble(*pending.pop(0))

    rest = iter(sims)
    first = next(rest, None)
    if first is not None:       # the first cell goes alone, at once
        dispatch(build(first), [0])
    groups: dict = {}
    for sim in rest:
        groups.setdefault(build(sim), []).append(len(built) - 1)
    for sig, idxs in groups.items():
        dispatch(sig, idxs)
    for p in pending:
        assemble(*p)
    return [out[i] for i in range(len(built))]
