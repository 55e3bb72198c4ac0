"""xLSTM blocks: chunkwise-parallel mLSTM + recurrent sLSTM (Beck et al.).

TPU adaptation (DESIGN.md §2): the mLSTM matrix memory is mathematically a
gated linear attention — we compute it in the same chunked dual form as the
Mamba2 SSD scan (batched chunk x chunk GEMMs on the MXU + a short scan over
chunk states), rather than porting the CUDA recurrent kernel. The matrix
memory (numerator) and the normalizer vector (denominator) are separate
states so the value dimension can TP-shard over the mesh.

Simplifications vs the paper (documented, tested for self-consistency):
* gates are computed in fp32 with clamped input-gate logits instead of the
  full max-stabilizer bookkeeping (exact for the magnitudes our configs
  produce; tests/test_models.py checks chunked == naive recurrence);
* sLSTM uses diagonal (per-channel) recurrent weights (block-diagonal
  simplification of the paper's per-head recurrent matrices).

xlstm-1.3b structure: 48 residual blocks, d_model 2048, 4 heads; every
``slstm_every``-th block is an sLSTM block, the rest mLSTM (7:1 ratio).
``d_ff=0``: there is no separate FFN — blocks carry their own 2x up/down
projections.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rmsnorm
from repro.models.sharding import ModelContext

MLSTM_CHUNK = 256
IGATE_CLAMP = 8.0


# --------------------------------------------------------------------------
# parameter init (shared shape for both block kinds => stackable for scan)
# --------------------------------------------------------------------------


def init_xlstm_params(key, d_model: int, n_heads: int, expand: int = 2) -> dict:
    d_in = expand * d_model
    ks = jax.random.split(key, 8)
    return {
        "norm": jnp.zeros((d_model,), jnp.float32),
        "up_proj": dense_init(ks[0], (d_model, 2 * d_in)),   # [x | z-gate]
        "qkv": dense_init(ks[1], (d_in, 3 * d_in)),
        "gates": dense_init(ks[2], (d_in, 2 * n_heads), scale=0.01),
        "gate_bias": jnp.concatenate([
            jnp.zeros((n_heads,), jnp.float32),              # input gates
            jnp.linspace(3.0, 6.0, n_heads, dtype=jnp.float32),  # forget
        ]),
        # sLSTM extras (diagonal recurrence + output gate); zero-cost for
        # mLSTM blocks but kept in the stacked pytree for scan uniformity
        "r_diag": dense_init(ks[3], (4, d_in), scale=0.01),
        "o_proj": dense_init(ks[4], (d_in, d_in), scale=0.01),
        "out_norm": jnp.zeros((d_in,), jnp.float32),
        "down_proj": dense_init(ks[5], (d_in, d_model)),
    }


# --------------------------------------------------------------------------
# mLSTM
# --------------------------------------------------------------------------


def mlstm_chunked(q, k, v, i_gate, f_gate, chunk: int = MLSTM_CHUNK,
                  init_state=None):
    """Chunkwise mLSTM.

    q,k,v: (B,S,nh,hd); i_gate,f_gate: (B,S,nh) raw logits.
    Returns (h (B,S,nh,hd), state) with state = (C (B,nh,hd_k,hd_v),
    n (B,nh,hd_k)) — numerator matrix memory and denominator vector kept
    SEPARATE (not a ones-column on V) so the value dimension can be
    TP-sharded over the mesh without touching the normalizer.

    Dual form per chunk: weight(i<-j) = exp(cumlf_i - cumlf_j + i_j),
    h_i = sum_j w_ij (q_i . k_j) v_j / max(|den_i|, 1).
    """
    B, S, nh, hd = q.shape
    if S % chunk != 0:
        chunk = S
    nc = S // chunk
    qf = q.astype(jnp.float32) * (hd ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))        # (B,S,nh) <=0
    ig = jnp.clip(i_gate.astype(jnp.float32), -IGATE_CLAMP, IGATE_CLAMP)

    qc = qf.reshape(B, nc, chunk, nh, hd)
    kc = kf.reshape(B, nc, chunk, nh, hd)
    vc = vf.reshape(B, nc, chunk, nh, hd)
    lfc = lf.reshape(B, nc, chunk, nh)
    igc = ig.reshape(B, nc, chunk, nh)

    cum = jnp.cumsum(lfc, axis=2)
    total = cum[:, :, -1]                                      # (B,nc,nh)

    # intra-chunk (mask the exponent BEFORE exp: masked entries would
    # overflow and poison gradients through where)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :] + igc[:, :, None, :, :]
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None]
    W = jnp.exp(jnp.where(mask, diff, -1e30))
    scores = jnp.einsum("bcinh,bcjnh->bcijn", qc, kc)          # (B,nc,Q,Q,nh)
    WS = W * scores
    h_intra = jnp.einsum("bcijn,bcjnd->bcind", WS, vc)
    den_intra = WS.sum(axis=3)                                 # (B,nc,Q,nh)

    # chunk states: C_c = sum_j w_j k_j v_j^T ; n_c = sum_j w_j k_j
    wstate = jnp.exp(total[:, :, None, :] - cum + igc)         # (B,nc,Q,nh)
    states = jnp.einsum("bcjn,bcjnh,bcjnd->bcnhd", wstate, kc, vc)
    nstates = jnp.einsum("bcjn,bcjnh->bcnh", wstate, kc)

    if init_state is None:
        s0 = (jnp.zeros((B, nh, hd, hd), jnp.float32),
              jnp.zeros((B, nh, hd), jnp.float32))
    else:
        s0 = init_state

    def step(carry, inp):
        sC, sn = carry
        stC, stn, tot = inp
        d = jnp.exp(tot)
        return (sC * d[:, :, None, None] + stC,
                sn * d[:, :, None] + stn), (sC, sn)

    (finC, finN), (prevC, prevN) = jax.lax.scan(
        step, s0, (states.transpose(1, 0, 2, 3, 4),
                   nstates.transpose(1, 0, 2, 3),
                   total.transpose(1, 0, 2)))
    prevC = prevC.transpose(1, 0, 2, 3, 4)     # (B,nc,nh,hd_k,hd_v)
    prevN = prevN.transpose(1, 0, 2, 3)        # (B,nc,nh,hd_k)

    ecum = jnp.exp(cum)
    h_inter = jnp.einsum("bcinh,bcnhd,bcin->bcind", qc, prevC, ecum)
    den_inter = jnp.einsum("bcinh,bcnh,bcin->bcin", qc, prevN, ecum)
    num = (h_intra + h_inter).reshape(B, S, nh, hd)
    den = (den_intra + den_inter).reshape(B, S, nh)
    out = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    return out.astype(q.dtype), (finC, finN)


def mlstm_decode_step(q, k, v, i_gate, f_gate, state):
    """Single step. q,k,v: (B,nh,hd); gates (B,nh);
    state = (C (B,nh,hd,hd), n (B,nh,hd))."""
    hd = q.shape[-1]
    C, n = state
    qf = q.astype(jnp.float32) * (hd ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    ig = jnp.clip(i_gate.astype(jnp.float32), -IGATE_CLAMP, IGATE_CLAMP)
    d = jnp.exp(lf)
    w = jnp.exp(ig)
    C_new = C * d[:, :, None, None] + w[:, :, None, None] * jnp.einsum(
        "bnh,bnd->bnhd", kf, vf)
    n_new = n * d[:, :, None] + w[:, :, None] * kf
    num = jnp.einsum("bnh,bnhd->bnd", qf, C_new)
    den = jnp.einsum("bnh,bnh->bn", qf, n_new)
    out = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    return out.astype(q.dtype), (C_new, n_new)


# --------------------------------------------------------------------------
# sequence-parallel mLSTM (ring mode): affine state exchange
# --------------------------------------------------------------------------
#
# Under sequence sharding (S over `model`), every projection/norm is
# position-wise (zero comm); only the inter-chunk state recurrence crosses
# ranks. That recurrence is an AFFINE map per rank r:
#     s_out = s_in * D_r + F_r
# (D_r = prod of the rank's chunk decays, F_r = its final local state with
# zero init), and affine maps compose associatively — so instead of a
# sequential 16-hop ring, each rank all-gathers every (D_r, F_r) pair once
# and computes its incoming state in closed form:
#     s_in(r) = sum_{r'<r} F_{r'} * prod_{r'<r''<r} D_{r''}
# Cost: one all_gather of (n_model, B, nh, hd, hd)-ish per layer plus a
# cheap first pass that computes only the chunk-state reductions.


def _mlstm_rank_summary(k, v, i_gate, f_gate, chunk: int):
    """Per-rank (log-decay total, final C, final n) with zero init —
    the affine map (D_r, F_r) of this rank's sequence slice."""
    B, S, nh, hd = k.shape
    nc = max(S // chunk, 1)
    kc = k.astype(jnp.float32).reshape(B, nc, -1, nh, hd)
    vc = v.astype(jnp.float32).reshape(B, nc, -1, nh, hd)
    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32)).reshape(B, nc, -1, nh)
    ig = jnp.clip(i_gate.astype(jnp.float32), -IGATE_CLAMP,
                  IGATE_CLAMP).reshape(B, nc, -1, nh)
    cum = jnp.cumsum(lf, axis=2)
    total = cum[:, :, -1]                                  # (B,nc,nh)
    w = jnp.exp(total[:, :, None, :] - cum + ig)
    states = jnp.einsum("bcjn,bcjnh,bcjnd->bcnhd", w, kc, vc)
    nstates = jnp.einsum("bcjn,bcjnh->bcnh", w, kc)

    def step(carry, inp):
        sC, sn = carry
        stC, stn, tot = inp
        d = jnp.exp(tot)
        return (sC * d[:, :, None, None] + stC,
                sn * d[:, :, None] + stn), None

    (fC, fN), _ = jax.lax.scan(
        step, (jnp.zeros_like(states[:, 0]), jnp.zeros_like(nstates[:, 0])),
        (states.transpose(1, 0, 2, 3, 4), nstates.transpose(1, 0, 2, 3),
         total.transpose(1, 0, 2)))
    logD = total.sum(axis=1)                               # (B,nh)
    return logD, fC, fN


def mlstm_seq_parallel(q, k, v, i_gate, f_gate, *, mesh, batch_axes,
                       chunk: int = MLSTM_CHUNK):
    """mLSTM with the sequence dim sharded over `model` via shard_map.
    q,k,v: (B, S, nh, hd) GLOBAL shapes, S sharded over `model`."""
    from jax.sharding import PartitionSpec as P
    n_model = mesh.shape["model"]
    io_spec = P(batch_axes, "model", None, None)
    g_spec = P(batch_axes, "model", None)

    def body(q_l, k_l, v_l, ig_l, fg_l):
        rank = jax.lax.axis_index("model")
        logD, fC, fN = _mlstm_rank_summary(k_l, v_l, ig_l, fg_l, chunk)
        # gather every rank's affine map: (n, B, nh, ...)
        logDs = jax.lax.all_gather(logD, "model")
        fCs = jax.lax.all_gather(fC, "model")
        fNs = jax.lax.all_gather(fN, "model")
        # incoming state: sum_{r<rank} F_r * exp(decay between r and rank)
        idx = jnp.arange(n_model)
        csum = jnp.cumsum(logDs, axis=0)                  # inclusive prefix
        upto = jnp.where(rank > 0, csum[jnp.maximum(rank - 1, 0)],
                         jnp.zeros_like(csum[0]))
        w_log = upto[None] - csum                         # (n, B, nh)
        mask = (idx < rank)[:, None, None]
        wgt = jnp.where(mask, jnp.exp(jnp.where(mask, w_log, 0.0)), 0.0)
        inC = jnp.einsum("rbn,rbnhd->bnhd", wgt, fCs)
        inN = jnp.einsum("rbn,rbnh->bnh", wgt, fNs)
        out, _ = mlstm_chunked(q_l, k_l, v_l, ig_l, fg_l, chunk=chunk,
                               init_state=(inC, inN))
        return out

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(io_spec, io_spec, io_spec, g_spec, g_spec),
        out_specs=io_spec, check_vma=False)(q, k, v, i_gate, f_gate)


# --------------------------------------------------------------------------
# sLSTM (sequential scalar memory)
# --------------------------------------------------------------------------


def slstm_scan(zifo, r_diag, n_heads: int, init_state=None):
    """zifo: (B, S, 4, d_in) pre-activations for z,i,f,o; r_diag: (4, d_in)
    diagonal recurrent weights. Returns (h (B,S,d_in), state)."""
    B, S, _, d_in = zifo.shape
    if init_state is None:
        init_state = (jnp.zeros((B, d_in), jnp.float32),
                      jnp.ones((B, d_in), jnp.float32),
                      jnp.zeros((B, d_in), jnp.float32))

    def step(carry, x_t):
        c, n, h_prev = carry
        pre = x_t.astype(jnp.float32) + r_diag * h_prev[:, None, :]
        z = jnp.tanh(pre[:, 0])
        i = jnp.exp(jnp.clip(pre[:, 1], -IGATE_CLAMP, IGATE_CLAMP))
        f = jax.nn.sigmoid(pre[:, 2])
        o = jax.nn.sigmoid(pre[:, 3])
        c_new = f * c + i * z
        n_new = f * n + i
        h = o * c_new / jnp.maximum(n_new, 1.0)
        return (c_new, n_new, h), h

    state, hs = jax.lax.scan(step, init_state,
                             zifo.transpose(1, 0, 2, 3))
    return hs.transpose(1, 0, 2), state


def slstm_decode_step(zifo, r_diag, state):
    """zifo: (B, 4, d_in); one step of the scan above."""
    c, n, h_prev = state
    pre = zifo.astype(jnp.float32) + r_diag * h_prev[:, None, :]
    z = jnp.tanh(pre[:, 0])
    i = jnp.exp(jnp.clip(pre[:, 1], -IGATE_CLAMP, IGATE_CLAMP))
    f = jax.nn.sigmoid(pre[:, 2])
    o = jax.nn.sigmoid(pre[:, 3])
    c_new = f * c + i * z
    n_new = f * n + i
    h = o * c_new / jnp.maximum(n_new, 1.0)
    return h, (c_new, n_new, h)


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------


def xlstm_block(x, params, *, n_heads: int, is_slstm: bool,
                ctx: Optional[ModelContext] = None,
                decode_state=None):
    """One residual xLSTM block (pre-norm, 2x up/down projection).
    x: (B, S, D). Returns (y, new_decode_state)."""
    B, S, D = x.shape
    d_in = params["up_proj"].shape[1] // 2
    h = rmsnorm(x, params["norm"])
    vtp = (ctx is not None and ctx.rules is not None
           and ctx.rules.get("xlstm_hd") and not is_slstm)
    if vtp:
        # merged column-parallel projections (vtp mode): qkv and the gates
        # consume the x-branch of up_proj LINEARLY, so fold
        # (up_x @ qkv) / (up_x @ gates) into single D->out weights — every
        # projection is column-sharded on the head dim with ZERO comms; the
        # block's only collective is the down_proj row-parallel all-reduce.
        # (Merge cost: D x d_in x 3d_in per layer, batch-free => negligible.)
        d_in_ = params["up_proj"].shape[1] // 2
        up_x = params["up_proj"][:, :d_in_]
        up_z = params["up_proj"][:, d_in_:]
        w_qkv = (up_x @ params["qkv"]).astype(h.dtype)      # (D, 3*d_in)
        w_gates = (up_x @ params["gates"]).astype(h.dtype)  # (D, 2*nh)
        z = h @ up_z.astype(h.dtype)
        xin = None
    else:
        up = h @ params["up_proj"].astype(h.dtype)
        xin, z = jnp.split(up, 2, axis=-1)

    if is_slstm:
        # map qkv projection output onto z,i,f,o pre-activations:
        # reuse qkv (3*d_in) + o_proj (d_in) for the 4 gates
        zi = xin @ params["qkv"].astype(xin.dtype)           # (B,S,3*d_in)
        og = xin @ params["o_proj"].astype(xin.dtype)        # (B,S,d_in)
        zifo = jnp.concatenate([zi, og], axis=-1).reshape(B, S, 4, d_in)
        if (ctx is not None and ctx.rules is not None
                and ctx.rules.get("_parallelism") == "ring"
                and decode_state is None):
            # sLSTM's h_{t-1} recurrence is not affine-composable: gather
            # the (cheap, scalar-memory) scan onto every rank
            zifo = ctx.shard(zifo, "batch", "attn_seq", None, None)
        if decode_state is None:
            hseq, new_state = slstm_scan(zifo, params["r_diag"], n_heads)
            if (ctx is not None and ctx.rules is not None
                    and ctx.rules.get("_parallelism") == "ring"):
                hseq = ctx.shard(hseq, "batch", "seq", None)
        else:
            h1, new_state = slstm_decode_step(
                zifo[:, 0], params["r_diag"], decode_state)
            hseq = h1[:, None]
        inner = hseq.astype(x.dtype)
    else:
        nh = n_heads
        hd = d_in // nh
        if vtp:
            qkv = h @ w_qkv
        else:
            qkv = xin @ params["qkv"].astype(xin.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, nh, hd)
        k = k.reshape(B, S, nh, hd)
        v = v.reshape(B, S, nh, hd)
        if ctx is not None and ctx.rules and ctx.rules.get("xlstm_hd"):
            # head-dim TP (hillclimb lever, parallelism="vtp"): when heads
            # are too few to shard, shard hd over `model` for q/k/v — the
            # projection GEMMs stay fully distributed, the qk-score
            # contraction all-reduces once, and the matrix memory's value
            # dim stays sharded end-to-end. (No constraint otherwise: let
            # GSPMD propagate the d_ff sharding of the projections.)
            q = ctx.shard(q, "batch", "seq", "ssm_heads", "xlstm_hd")
            k = ctx.shard(k, "batch", "seq", "ssm_heads", "xlstm_hd")
            v = ctx.shard(v, "batch", "seq", "ssm_heads", "xlstm_hd")
        gates = (h @ w_gates if vtp
                 else xin @ params["gates"].astype(xin.dtype))  # (B,S,2*nh)
        gates = gates.astype(jnp.float32) + params["gate_bias"][None, None, :]
        ig, fg = jnp.split(gates, 2, axis=-1)
        ring = (ctx is not None and ctx.rules is not None
                and ctx.rules.get("_parallelism") == "ring"
                and decode_state is None and ctx.mesh is not None)
        if ring:
            n_model = ctx.mesh.shape["model"]
            hseq = mlstm_seq_parallel(
                q, k, v, ig, fg, mesh=ctx.mesh,
                batch_axes=ctx.rules.get("batch"),
                chunk=min(MLSTM_CHUNK, max(S // n_model, 1)))
            new_state = None
        elif decode_state is None:
            hseq, new_state = mlstm_chunked(q, k, v, ig, fg,
                                            chunk=min(MLSTM_CHUNK, S))
        else:
            h1, new_state = mlstm_decode_step(
                q[:, 0], k[:, 0], v[:, 0], ig[:, 0], fg[:, 0], decode_state)
            hseq = h1[:, None]
        inner = hseq.reshape(B, S, d_in).astype(x.dtype)

    inner = rmsnorm(inner, params["out_norm"]) * jax.nn.silu(z)
    out = inner @ params["down_proj"].astype(inner.dtype)
    return x + out, new_state


def init_xlstm_state(batch: int, d_model: int, n_heads: int,
                     is_slstm: bool, expand: int = 2):
    d_in = expand * d_model
    if is_slstm:
        return (jnp.zeros((batch, d_in), jnp.float32),
                jnp.ones((batch, d_in), jnp.float32),
                jnp.zeros((batch, d_in), jnp.float32))
    hd = d_in // n_heads
    return (jnp.zeros((batch, n_heads, hd, hd), jnp.float32),
            jnp.zeros((batch, n_heads, hd), jnp.float32))


# --------------------------------------------------------------------------
# model level (xlstm-1.3b): heterogeneous blocks => python-unrolled loop
# --------------------------------------------------------------------------


def slstm_flags(cfg) -> list[bool]:
    if cfg.slstm_every <= 0:
        return [False] * cfg.n_layers
    return [(i + 1) % cfg.slstm_every == 0 for i in range(cfg.n_layers)]


def init_xlstm_lm_params(key, cfg) -> dict:
    from repro.models.layers import dense_init
    kb, ke, kh = jax.random.split(key, 3)
    per = [init_xlstm_params(k, cfg.d_model, cfg.n_heads)
           for k in jax.random.split(kb, cfg.n_layers)]
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    return {
        "embed": dense_init(ke, (cfg.vocab_size, cfg.d_model)),
        "blocks": stack,
        "final_norm": jnp.zeros((cfg.d_model,)),
        "lm_head": dense_init(kh, (cfg.d_model, cfg.vocab_size)),
    }


def xlstm_param_specs(cfg) -> dict:
    return {
        "embed": ("vocab", "d_model"),
        "blocks": {
            "norm": ("layers", "d_model"),
            "up_proj": ("layers", "d_model", None),
            "qkv": ("layers", "d_model", "d_ff"),
            "gates": ("layers", "d_model", None),
            "gate_bias": ("layers", None),
            "r_diag": ("layers", None, "d_model"),
            "o_proj": ("layers", "d_model", "d_ff"),
            "out_norm": ("layers", "d_model"),
            "down_proj": ("layers", "d_model", None),
        },
        "final_norm": ("d_model",),
        "lm_head": ("d_model", "vocab"),
    }


def xlstm_forward(params, batch, cfg, ctx: Optional[ModelContext] = None,
                  last_only: bool = False):
    from repro.models.layers import embed as embed_fn, unembed
    ctx = ctx or ModelContext()
    x = embed_fn(batch["tokens"], params["embed"].astype(jnp.bfloat16), ctx)
    flags = slstm_flags(cfg)

    def make_block(i, flag):
        def blk(x):
            p_i = jax.tree.map(lambda a: a[i], params["blocks"])
            y, _ = xlstm_block(x, p_i, n_heads=cfg.n_heads, is_slstm=flag,
                               ctx=ctx)
            return y
        return jax.checkpoint(blk) if cfg.remat else blk

    for i, flag in enumerate(flags):
        x = make_block(i, flag)(x)
    if last_only:
        x = x[:, -1:]
    x = rmsnorm(x, params["final_norm"])
    logits = unembed(x, params["lm_head"], 0.0, ctx)
    if ctx.distributed:
        logits = ctx.shard(logits, "batch", "seq", "vocab")
    return logits


def init_xlstm_lm_cache(cfg, batch: int, max_len: int = 0) -> list:
    return [init_xlstm_state(batch, cfg.d_model, cfg.n_heads, f)
            for f in slstm_flags(cfg)]


def xlstm_cache_specs(cfg) -> list:
    out = []
    for f in slstm_flags(cfg):
        if f:
            out.append((("batch", None),) * 3)
        else:
            out.append((("batch", "ssm_heads", None, "xlstm_hd"),
                        ("batch", "ssm_heads", None)))
    return out


def xlstm_decode_step(params, cache, tokens, pos, cfg,
                      ctx: Optional[ModelContext] = None):
    from repro.models.layers import embed as embed_fn, unembed
    ctx = ctx or ModelContext()
    x = embed_fn(tokens[:, None], params["embed"].astype(jnp.bfloat16), None)
    new_cache = []
    for i, flag in enumerate(slstm_flags(cfg)):
        p_i = jax.tree.map(lambda a: a[i], params["blocks"])
        x, st = xlstm_block(x, p_i, n_heads=cfg.n_heads, is_slstm=flag,
                            ctx=ctx, decode_state=cache[i])
        new_cache.append(st)
    x = rmsnorm(x[:, 0], params["final_norm"])
    logits = unembed(x, params["lm_head"], 0.0, ctx)
    return logits, new_cache
