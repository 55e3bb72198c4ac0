"""JAX-native StreamSim engine (``engine="jax"``).

:class:`JaxStreamSim` ports the vectorized engine's hot kernels to
``jax.jit`` device programs and keeps everything else — the batch event
loop, the hop-graph resolution, the publish/deliver choreography — from
:class:`~repro.core.vectorized.VectorizedStreamSim`:

* **prefix-scan FIFO** — ``_fifo_scan`` becomes two
  ``lax.associative_scan`` prefix scans (sum, max), ``jax.vmap``-ed over the trailing lane axis, so one
  device program serves every stacked seed-lane of a resource batch;
* **cohort admission** — the per-message arrival-order admission walk
  (byte-cap rejects, credit-threshold crossings, high-water marks)
  becomes one ``lax.scan`` over the cohort with the per-queue drain
  counts precomputed by a vmapped ``searchsorted``;
* **masked depart stores** — the per-lane depart *heaps* are replaced
  by ``(entries, lanes)`` time arrays plus a consumed mask; pops are
  masked reductions (``segment-min``-style ``where``/``argsort``
  kernels) instead of heap mutations;
* **windowed broker pump** — the fast path's strict round-robin
  split/gate arithmetic is one fused gather, and the slow path's
  per-message ``next_delivery`` selection is a ``lax.scan`` over a
  fixed-shape chunk carrying the rotated consumer order and window
  gates.

**Pad-and-mask contract.**  Every kernel call pads its cohort axis to
the next power of two (bounding jit recompiles to ``O(log n)`` distinct
shapes) with *inert* values — ``+inf`` arrival clocks, ``consumed=True``
depart rows, ``valid=False`` scan steps — that can never perturb a real
lane's arithmetic.  ``tests/test_jax_engine.py`` property-tests this
invariance.

**x64 is forced, scoped.**  Time arithmetic must match the float64
NumPy engines (under f32 a 1e-4 s service hold vanishes against a 1e3 s
clock), but this repo's model/kernel stack runs JAX at default x32 —
so every engine kernel runs under :func:`repro.compat.scoped_x64`
(``jax.enable_x64(True)`` as a context) instead of flipping the global
flag.  On a TPU, which has no native float64, XLA emulates it.

The module imports without JAX installed; only constructing
:class:`JaxStreamSim` (or calling a kernel) requires it.
:func:`~repro.core.vectorized.run_many` consults :func:`jax_supported`
and falls back to the vectorized engine per cell when JAX is missing.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import numpy as np

from repro.core.simulator import ENGINES, ExperimentSpec, RunResult
from repro.core.vectorized import VectorizedStreamSim


def jax_available() -> bool:
    """True when jax is installed.  Only a missing package reads as
    unavailable: an installed jax that fails to import raises, rather
    than turning jax cells into quiet vectorized runs."""
    try:
        import jax  # noqa: F401
    except ImportError:
        return False
    return True


def jax_supported(spec: ExperimentSpec) -> tuple[bool, str]:
    """Can the JAX engine take this cell?  Returns ``(ok, reason)``.

    The engine inherits the full vectorized event loop, so every cell
    shape the vectorized engine accepts is supported except chaos cells
    (``params.chaos``): the masked depart-store kernels assume a static
    topology, so chaos cells fall back to the vectorized engine.
    ``run_many`` records the fallback per cell (the result's
    ``spec.params.engine``)."""
    if not jax_available():
        return False, "jax is not importable in this environment"
    if spec.params.chaos is not None:
        return False, ("chaos schedules mutate topology mid-run; the "
                       "jax depart-store kernels are static — falling "
                       "back to the vectorized engine")
    return True, ""


def _pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) — the pad-and-mask shape
    bucket, bounding distinct jit shapes per call site to O(log n)."""
    return 1 << max(0, int(n) - 1).bit_length()


#: jax arrays flow through the kernels, but jax is only imported
#: lazily inside _kernels — Any keeps the annotations honest without
#: a module-level jax dependency
Array = Any


@functools.lru_cache(maxsize=1)
def _kernels() -> Any:
    """Build (once) the jitted kernel set.  Raises ImportError without
    JAX.  Every kernel is wrapped to run under a scoped x64 context."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.compat import scoped_x64

    def x64(fn: Callable[..., Any]) -> Callable[..., Any]:
        jfn = jax.jit(fn)

        @functools.wraps(fn)
        def call(*args: Any) -> Any:
            with scoped_x64():
                return jfn(*args)
        return call

    def fifo1(a: Array, h: Array, carry: Array) -> Array:
        # e_j = max(a_j, e_{j-1}) + h_j in closed form (see _fifo_scan)
        # associative scans, not jnp.cumsum / lax.cummax: those lower to
        # a reduce-window whose 64-bit emulation the TPU compiler takes
        # a minute to compile at 128 rows
        a = jnp.maximum(a, carry)
        H = lax.associative_scan(jnp.add, h)
        return H + lax.associative_scan(jnp.maximum, a - (H - h))

    class K:
        fifo_scan_1d = x64(fifo1)
        #: the lane axis is a vmap over the solo scan — the identity
        #: test_fifo_scan_lane_axis_matches_per_lane property-tests
        fifo_scan_lanes = x64(jax.vmap(fifo1, in_axes=(1, 1, 0),
                                       out_axes=1))
        #: (cell x lane)-batched scan: one device program serves a whole
        #: campaign round's worth of (C, N, L) FIFO scans — the NumPy
        #: engine must loop C python calls for the same work
        fifo_scan_cells = x64(jax.vmap(jax.vmap(fifo1,
                                                in_axes=(1, 1, 0),
                                                out_axes=1)))

        @x64
        def pop_until(t: Array, used: Array,
                      thresh: Array) -> tuple[Array, Array, Array]:
            """Masked depart-cursor advance: consume every recorded,
            unconsumed depart <= thresh.  Returns (n_popped, last_pop_t,
            used')."""
            ready = (~used) & (t <= thresh)
            return (ready.sum(),
                    jnp.max(jnp.where(ready, t, -jnp.inf)),
                    used | ready)

        @x64
        def pop_k(t: Array, used: Array,
                  k: Array) -> tuple[Array, Array, Array]:
            """Consume the k earliest unconsumed departs (the heap's
            pop-to-target).  Returns (n_popped, last_pop_t, used')."""
            masked = jnp.where(used, jnp.inf, t)
            order = jnp.argsort(masked)
            npop = jnp.minimum(k, (~used).sum())
            sel = jnp.arange(t.shape[0]) < npop
            return (npop,
                    jnp.max(jnp.where(sel, masked[order], -jnp.inf)),
                    used.at[order].set(used[order] | sel))

        @x64
        def next_drain(t: Array, used: Array) -> Array:
            """Masked segment-min: the earliest unconsumed depart
            (+inf when none is recorded)."""
            return jnp.min(jnp.where(used, jnp.inf, t))

        @x64
        def admit_walk(t: Array, valid: Array, dep_sorted: Array,
                       dep0: Array, n_enq0: Array, caps: Array,
                       credits: Array
                       ) -> tuple[Array, Array, Array, Array, Array]:
            """One lane's arrival-order admission walk as a lax.scan.

            ``t``: (M,) member clocks (sorted; +inf pads), ``valid``:
            (M,) real-member mask, ``dep_sorted``: (Q, D) each tracked
            queue's sorted unconsumed depart times (+inf pads),
            ``dep0``/``n_enq0``: (Q,) cursor/enqueue counts at entry,
            ``caps``/``credits``: (Q,) with a huge sentinel for
            untracked limits.  Returns per-member (admitted,
            first_full_queue, blocked_queue) plus per-queue admitted
            high-water marks and the admitted count."""
            Q = dep_sorted.shape[0]
            # total departed at each member's clock, per queue — the
            # lazy heap pops are monotone in t, so a prefix count of
            # the sorted drains reproduces the cursor exactly
            dc = dep0[:, None] + jax.vmap(
                lambda d: jnp.searchsorted(d, t, side="right"))(
                    dep_sorted)                      # (Q, M)

            def step(adm: Array, xs: tuple[Array, Array]
                     ) -> tuple[Array, tuple[Array, Array, Array, Array]]:
                dci, ok = xs
                backlog = n_enq0 + adm - dci         # (Q,) pre-admit
                fullv = backlog >= caps
                first_full = jnp.where(
                    ok, jnp.where(fullv.any(), jnp.argmax(fullv), Q),
                    -1)
                admit = ok & ~fullv.any()
                one = admit.astype(n_enq0.dtype)
                backlog_after = backlog + one
                crossed = backlog_after > credits
                blocked = jnp.where(admit & crossed.any(),
                                    jnp.argmax(crossed), Q)
                return adm + one, (admit, first_full, backlog_after,
                                   blocked)

            n_adm, (admit, first_full, backlog_after, blocked) = \
                lax.scan(step, jnp.int64(0), (dc.T, valid))
            hwm = jnp.max(jnp.where(admit[:, None], backlog_after, -1),
                          axis=0)
            return admit, first_full, blocked, hwm, n_adm

        @x64
        def rr_assign(t: Array, assigned0: Array, offs: Array,
                      ack_win: Array, P: Array) -> tuple[Array, Array]:
            """The pump fast path's round-robin split as one fused
            gather: message r goes to consumer r % k; its depart gates
            on the ack that freed its window slot, read from the
            per-consumer ack window ``ack_win[x] = ack_time[offs[x]:]``
            (NaN pads past the acked prefix are unreachable on this
            path).  ``t`` is (n,) or (n, lanes)."""
            n = t.shape[0]
            k = assigned0.shape[0]
            cons_of = jnp.arange(n) % k
            j_all = assigned0[cons_of] + jnp.arange(n) // k
            idx = jnp.clip(j_all - P - offs[cons_of], 0,
                           ack_win.shape[1] - 1)
            g = ack_win[cons_of, idx]
            m = j_all >= P
            if g.ndim == 2:
                m = m[:, None]
            return j_all, jnp.maximum(t, jnp.where(m, g, -jnp.inf))

        @x64
        def assign_chunk(tv: Array, t0: Array, valid: Array, g0: Array,
                         assigned0: Array, offs: Array, ack_win: Array,
                         P: Array) -> tuple[Array, ...]:
            """The pump slow path (the heap broker's per-message
            ``next_delivery`` in virtual time) as a lax.scan.

            ``tv``: (T, L) member ready clocks (pads invalid), ``t0``:
            (T,) pilot clocks, ``g0``: (k, L) initial window gates
            (NaN = re-opening unknown), ``ack_win``: (k, W, L) each
            consumer's upcoming ack clocks.  Carries the rotated
            round-robin order, per-consumer assignment counts and the
            stopped flag; emits per-step (assigned?, consumer, tag,
            depart)."""
            k = g0.shape[0]
            W = ack_win.shape[1]

            def step(carry: tuple[Array, Array, Array, Array],
                     xs: tuple[Array, Array, Array]
                     ) -> tuple[tuple[Array, Array, Array, Array],
                                tuple[Array, Array, Array, Array]]:
                g, order, nass, stopped = carry
                tvi, ti, ok = xs
                go = g[order]                        # (k, L)
                go0 = go[:, 0]                       # pilot column
                open_m = go0 <= ti                   # NaN -> False
                finite = jnp.isfinite(go0)
                can = ok & ~stopped & (open_m.any() | finite.any())
                pos = jnp.where(open_m.any(), jnp.argmax(open_m),
                                jnp.argmin(jnp.where(finite, go0,
                                                     jnp.inf)))
                x = order[pos]
                depart = jnp.maximum(tvi, go[pos])
                j = assigned0[x] + nass[x]
                idx = jnp.clip(j + 1 - P - offs[x], 0, W - 1)
                gnew = jnp.where(j + 1 >= P, ack_win[x, idx], -jnp.inf)
                g2 = jnp.where(can, g.at[x].set(gnew), g)
                rot = jnp.where(jnp.arange(k) < pos, order,
                                jnp.roll(order, -1)).at[k - 1].set(x)
                order2 = jnp.where(can, rot, order)
                nass2 = jnp.where(can, nass.at[x].add(1), nass)
                return ((g2, order2, nass2, stopped | (ok & ~can)),
                        (can, x, j, depart))

            init = (g0, jnp.arange(k), jnp.zeros(k, jnp.int64), False)
            (g, order, nass, _), outs = lax.scan(step, init,
                                                 (tv, t0, valid))
            return (order, nass) + outs

    return K


def _jax_fifo_scan(a: np.ndarray, h: np.ndarray, carry: Any) -> np.ndarray:
    """Drop-in ``_fifo_scan`` port: pad the cohort axis to a power of
    two with inert ``+inf`` arrivals / zero holds, run the jitted scan
    (lane-vmapped when a lane axis is present), slice the pads off."""
    K = _kernels()
    a = np.asarray(a, dtype=np.float64)
    h = np.broadcast_to(np.asarray(h, dtype=np.float64), a.shape)
    n = a.shape[0]
    m = _pow2(n)
    if a.ndim == 1:
        ap = np.full(m, np.inf)
        hp = np.zeros(m)
        ap[:n], hp[:n] = a, h
        out = K.fifo_scan_1d(ap, hp, float(np.asarray(carry)))
        return np.asarray(out)[:n]
    L = a.shape[1]
    ap = np.full((m, L), np.inf)
    hp = np.zeros((m, L))
    ap[:n], hp[:n] = a, h
    c = np.broadcast_to(np.asarray(carry, dtype=np.float64), (L,))
    return np.asarray(K.fifo_scan_lanes(ap, hp, c))[:n]


#: sentinel for "no cap/credit limit" inside integer kernels (far above
#: any reachable backlog, far below int64 overflow under += 1)
_NO_LIMIT = np.int64(2) ** 62


class JaxStreamSim(VectorizedStreamSim):
    """The vectorized engine with its hot kernels on JAX devices.

    Same constructor/run/stacking contract as the base class; only the
    kernel layer differs, so parity vs the heap engine inherits the
    vectorized engine's tolerance bands (the arithmetic is the same
    float64 recurrences, re-associated at worst at the 1e-16 level).
    """

    #: device batches amortize better over wide lane axes, so jax
    #: groups stack 4x more seed-lanes per run than the NumPy engine
    STACK_MAX_LANES = 64

    PATH = "jax-cohort"

    _scan_impl = staticmethod(_jax_fifo_scan)

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        if not jax_available():
            raise ImportError(
                "engine='jax' requires jax; install jax or use "
                "engine='vectorized' (run_many falls back automatically)")
        spec = args[0] if args else kwargs.get("spec")
        if spec is not None and spec.params.chaos is not None:
            raise ValueError(
                "engine='jax' does not support chaos schedules (the "
                "depart-store kernels are static); use "
                "engine='vectorized' (run_many falls back automatically)")
        self._K = _kernels()
        super().__init__(*args, **kwargs)
        #: why this cell does not run on the whole-run device program
        #: ("" when it does); results record the path as ``RunResult.path``
        self.device_loop_why = "jax_device_loop not requested"
        if self.p.jax_device_loop:
            from repro.core import jax_device_loop
            self.device_loop_why = jax_device_loop._device_loop_ok(self)[1]

    # -- whole-run device program (opt-in; repro.core.jax_device_loop) -----
    def _use_device_loop(self) -> bool:
        """True when ``params.jax_device_loop`` requests the whole-run
        device program *and* this cell is wave-formulated.  Off by
        default: the device loop trades the cohort engines' event
        ordering for one fused ``lax.scan``, so it matches them at the
        ``device_loop.*`` parity bands instead of bit-for-bit."""
        return not self.device_loop_why

    def run(self) -> RunResult:
        if self._use_device_loop():
            from repro.core import jax_device_loop
            return jax_device_loop.run_wave_results(self)[0]
        return super().run()

    def run_stacked(self) -> list[RunResult]:
        if self._use_device_loop():
            from repro.core import jax_device_loop
            return jax_device_loop.run_wave_results(self)
        return super().run_stacked()

    # -- masked depart store (replaces the per-lane heaps) -----------------
    def _queue_state(self, qkey: tuple, consumers: list[int],
                     size: int, *,
                     credit: Optional[int] = None,
                     cap_msgs: Optional[int] = None) -> dict:
        fresh = qkey not in self._queues
        q = super()._queue_state(qkey, consumers, size, credit=credit,
                                 cap_msgs=cap_msgs)
        if fresh and q["track"]:
            L = self._lanes
            # masked store: one row per recorded release (all lanes),
            # consumed flags per (entry, lane); padded rows are born
            # consumed with +inf clocks — inert under every kernel
            q["depart_heap"] = None
            q["dep_t"] = np.empty((0, L))
            q["dep_used"] = np.empty((0, L), dtype=bool)
            q["dep_n"] = 0
        return q

    def _dep_col(self, q: dict, lane: int) -> tuple[np.ndarray,
                                                    np.ndarray]:
        """One lane's depart column, padded to the pow2 shape bucket
        (+inf / consumed pads)."""
        n = q["dep_n"]
        m = _pow2(n)
        t = np.full(m, np.inf)
        u = np.ones(m, dtype=bool)
        t[:n] = q["dep_t"][:n, lane]
        u[:n] = q["dep_used"][:n, lane]
        return t, u

    def _record_departs(self, q: dict, departs: np.ndarray) -> None:
        if not q["track"]:
            return
        cols = np.asarray(departs, dtype=np.float64).reshape(
            departs.shape[0], self._lanes)
        n0, m = q["dep_n"], cols.shape[0]
        if n0 + m > q["dep_t"].shape[0]:
            cap = max(n0 + m, 2 * q["dep_t"].shape[0], 64)
            t = np.full((cap, self._lanes), np.inf)
            u = np.ones((cap, self._lanes), dtype=bool)
            t[:n0] = q["dep_t"][:n0]
            u[:n0] = q["dep_used"][:n0]
            q["dep_t"], q["dep_used"] = t, u
        q["dep_t"][n0:n0 + m] = cols
        q["dep_used"][n0:n0 + m] = False
        q["dep_n"] = n0 + m
        q["released"] += m
        if q["deferred"]:
            self._try_resume(q)

    def _pop_lane(self, q: dict, lane: int, t: float) -> None:
        n = q["dep_n"]
        if n == 0:
            return
        col, used = self._dep_col(q, lane)
        cnt, last, used2 = self._K.pop_until(col, used, float(t))
        cnt = int(cnt)
        if cnt:
            q["dep_used"][:n, lane] = np.asarray(used2)[:n]
            q["departed"][lane] += cnt
            q["last_pop_t"][lane] = float(last)

    def _pop_to_target(self, q: dict, lane: int, target: int) -> None:
        need = int(target) - int(q["departed"][lane])
        n = q["dep_n"]
        if need <= 0 or n == 0:
            return
        col, used = self._dep_col(q, lane)
        cnt, last, used2 = self._K.pop_k(col, used, need)
        cnt = int(cnt)
        if cnt:
            q["dep_used"][:n, lane] = np.asarray(used2)[:n]
            q["departed"][lane] += cnt
            q["last_pop_t"][lane] = float(last)

    def _next_drain(self, q: dict, lane: int) -> Optional[float]:
        if q["dep_n"] == 0:
            return None
        nd = float(self._K.next_drain(*self._dep_col(q, lane)))
        return nd if np.isfinite(nd) else None

    # -- cohort admission as one device scan -------------------------------
    def _admit_walk(self, tracked: list, lane: int, ks: np.ndarray,
                    T: np.ndarray) -> tuple[np.ndarray, list]:
        m = ks.size
        if m == 0:
            return np.zeros(0, dtype=int), []
        Q = len(tracked)
        tl = np.asarray(T[ks, lane], dtype=np.float64)
        deps = []
        for q in tracked:
            n = q["dep_n"]
            col = q["dep_t"][:n, lane]
            deps.append(np.sort(col[~q["dep_used"][:n, lane]]))
        D = _pow2(max((d.size for d in deps), default=1))
        dep_pad = np.full((Q, D), np.inf)
        for qi, d in enumerate(deps):
            dep_pad[qi, :d.size] = d
        caps = np.array([q["cap"] if q["cap"] is not None else _NO_LIMIT
                         for q in tracked], dtype=np.int64)
        credits = np.array(
            [q["credit"] if q["credit"] is not None else _NO_LIMIT
             for q in tracked], dtype=np.int64)
        n_enq0 = np.array([q["n_enq"][lane] for q in tracked],
                          dtype=np.int64)
        dep0 = np.array([q["departed"][lane] for q in tracked],
                        dtype=np.int64)
        M = _pow2(m)
        t_pad = np.full(M, np.inf)
        t_pad[:m] = tl
        valid = np.zeros(M, dtype=bool)
        valid[:m] = True
        admit, first_full, blocked_q, hwm, n_adm = self._K.admit_walk(
            t_pad, valid, dep_pad, dep0, n_enq0, caps, credits)
        admit = np.asarray(admit)[:m]
        first_full = np.asarray(first_full)[:m]
        blocked_q = np.asarray(blocked_q)[:m]
        n_adm = int(n_adm)
        for qi, q in enumerate(tracked):
            # sync the store to the walk: queue qi was popped by every
            # member the per-queue loop reached (first_full >= qi)
            reach = first_full >= qi
            if reach.any():
                self._pop_lane(q, lane, float(tl[reach].max()))
            q["n_enq"][lane] += n_adm
            if n_adm:
                q["hwm"][lane] = max(q["hwm"][lane], int(hwm[qi]))
        blocked = [(int(ks[i]), tracked[int(blocked_q[i])])
                   for i in np.nonzero(admit & (blocked_q < Q))[0]]
        return ks[admit], blocked

    # -- windowed broker pump kernels --------------------------------------
    def _rr_assign(self, ids: list, t_sl: np.ndarray, P: int
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        n_rem = t_sl.shape[0]
        k = len(ids)
        cnts = [(n_rem - r + k - 1) // k for r in range(k)]
        chans = [self._chan(c) for c in ids]
        for ch, cnt in zip(chans, cnts):
            self._chan_grow(ch, cnt)
        assigned0 = np.array([ch["assigned"] for ch in chans],
                             dtype=np.int64)
        offs = np.maximum(assigned0 - P, 0)
        W = _pow2(max(cnts) + 1)
        lane_tail = t_sl.shape[1:]
        ack_win = np.full((k, W) + lane_tail, np.nan)
        for x, ch in enumerate(chans):
            seglen = min(W, ch["ack_time"].shape[0] - int(offs[x]))
            if seglen > 0:
                ack_win[x, :seglen] = \
                    ch["ack_time"][int(offs[x]):int(offs[x]) + seglen]
        M = _pow2(n_rem)
        t_pad = np.full((M,) + lane_tail, np.inf)
        t_pad[:n_rem] = t_sl
        j_all, depart = self._K.rr_assign(t_pad, assigned0, offs,
                                          ack_win, int(P))
        for ch, cnt in zip(chans, cnts):
            ch["assigned"] += cnt
        cons = np.array(ids)[np.arange(n_rem) % k]
        return (cons, np.asarray(j_all)[:n_rem],
                np.asarray(depart)[:n_rem])

    def _assign_chunk(self, seg: dict, ids: list, P: int
                      ) -> tuple[list, list]:
        chunk = max(1, self.p.ack_batch)
        take = min(chunk, seg["idx"].size - seg["pos"])
        if take <= 0:
            return [], list(ids)
        k = len(ids)
        L = self._lanes
        solo = L == 1
        chans = [self._chan(c) for c in ids]
        for ch in chans:
            self._chan_grow(ch, take)
        assigned0 = np.array([ch["assigned"] for ch in chans],
                             dtype=np.int64)
        offs = np.maximum(assigned0 + 1 - P, 0)
        W = _pow2(take + 1)
        ack_win = np.full((k, W, L), np.nan)
        g0 = np.empty((k, L))
        for x, ch in enumerate(chans):
            at = ch["ack_time"].reshape(ch["ack_time"].shape[0], L)
            j = int(assigned0[x])
            g0[x] = -np.inf if j < P else at[j - P]
            seglen = min(W, at.shape[0] - int(offs[x]))
            if seglen > 0:
                ack_win[x, :seglen] = at[int(offs[x]):int(offs[x])
                                         + seglen]
        T = _pow2(take)
        sl = slice(seg["pos"], seg["pos"] + take)
        tv = np.full((T, L), np.inf)
        tv[:take] = seg["t"][sl].reshape(take, L)
        t0 = np.full(T, np.inf)
        t0[:take] = np.asarray(_lane0_col(seg["t"][sl]))
        valid = np.zeros(T, dtype=bool)
        valid[:take] = True
        order, nass, can, xs, js, departs = self._K.assign_chunk(
            tv, t0, valid, g0, assigned0, offs, ack_win, int(P))
        can = np.asarray(can)
        xs, js = np.asarray(xs), np.asarray(js)
        departs = np.asarray(departs)
        n_t = int(can.sum())          # stop/pad flags form a suffix
        rel = []
        for i in range(n_t):
            x = int(xs[i])
            d = departs[i]
            rel.append((seg["idx"][seg["pos"]], ids[x], int(js[i]),
                        float(d[0]) if solo else d.copy()))
            seg["pos"] += 1
        for x, ch in enumerate(chans):
            ch["assigned"] += int(nass[x])
        return rel, [ids[int(x)] for x in np.asarray(order)]


def _lane0_col(a: np.ndarray) -> np.ndarray:
    return a if a.ndim == 1 else a[:, 0]


ENGINES["jax"] = JaxStreamSim
