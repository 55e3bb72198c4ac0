"""Mixture-of-Experts layer with two implementations.

* ``dense`` — every expert computed for every token, combined by gate
  weights. O(E) FLOPs: only for reduced smoke configs and as the numerical
  oracle for the EP path.
* ``ep`` — expert parallelism over the ``model`` mesh axis via
  ``shard_map`` + fixed-capacity ``all_to_all`` (the TPU-idiomatic dispatch:
  sort-by-expert, scatter into per-expert capacity slots, A2A to expert
  shards, batched GEMMs, A2A back, weighted combine). Tokens over capacity
  are dropped (standard Switch-style; capacity_factor controls slack) and
  their residual passes through untouched.

Weights layout (stacked per layer by the caller):
  router: (D, E)
  wi:     (E, D, 2F)   fused gate+up (SwiGLU experts)
  wo:     (E, F, D)
  shared experts (n_s >= 1, e.g. Moonlight): wi_s: (D, 2*F*n_s), wo_s: (F*n_s, D)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import swiglu
from repro.models.sharding import ModelContext


def router_probs(x: jax.Array, w_router: jax.Array, k: int):
    """Top-k routing with renormalized softmax gates (fp32 router)."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                            # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def load_balancing_loss(probs: jax.Array, idx: jax.Array, n_experts: int):
    """Switch-transformer aux loss: E * sum_e f_e * p_e."""
    counts = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(idx.size, 1)
    p = probs.mean(axis=0)
    return n_experts * jnp.sum(f * p)


def _expert_ffn(xs: jax.Array, wi: jax.Array, wo: jax.Array) -> jax.Array:
    """xs: (E, C, D), wi: (E, D, 2F), wo: (E, F, D) -> (E, C, D)."""
    h = jnp.einsum("ecd,edf->ecf", xs, wi.astype(xs.dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(xs.dtype))


# --------------------------------------------------------------------------
# dense oracle
# --------------------------------------------------------------------------


def moe_dense(x: jax.Array, params: dict, k: int,
              ctx: Optional[ModelContext] = None) -> jax.Array:
    """x: (B, S, D). Computes all experts densely; exact combine."""
    B, S, D = x.shape
    E = params["router"].shape[1]
    xt = x.reshape(B * S, D)
    gates, idx, _ = router_probs(xt, params["router"], k)
    # (E, T, D) all-experts compute
    h = jnp.einsum("td,edf->etf", xt, params["wi"].astype(xt.dtype))
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    ye = jnp.einsum("etf,efd->etd", h,
                    params["wo"].astype(xt.dtype))                 # (E, T, D)
    onehot = jax.nn.one_hot(idx, E, dtype=ye.dtype)                # (T, k, E)
    combine = jnp.einsum("tke,tk->te", onehot, gates.astype(ye.dtype))
    out = jnp.einsum("te,etd->td", combine, ye)
    return out.reshape(B, S, D)


# --------------------------------------------------------------------------
# expert-parallel shard_map path
# --------------------------------------------------------------------------


def _ep_local(xt_full, router, wi, wo, *, k: int, n_experts: int,
              capacity_factor: float, model_axis: str, n_model: int,
              tokens_replicated: bool):
    """Per-device body. xt_full: (T_full, D) local tokens; wi/wo hold
    E_loc experts; router replicated.

    When the batch shards over data only (megatron TP), tokens are
    REPLICATED across the EP/model axis: each EP rank dispatches only ITS
    1/n_model token slice (otherwise every rank ships and computes the same
    tokens and the expert GEMMs run n_model x duplicated) and outputs are
    re-assembled with one all_gather. Under FSDP (batch sharded over model
    too) every rank already owns distinct tokens — no slice/gather."""
    T_full = xt_full.shape[0]
    E = n_experts
    E_loc = wi.shape[0]
    if tokens_replicated and n_model > 1 and T_full % n_model == 0:
        T = T_full // n_model
        rank = jax.lax.axis_index(model_axis)
        xt = jax.lax.dynamic_slice_in_dim(xt_full, rank * T, T, axis=0)
    else:
        T = T_full
        xt = xt_full
    gates, idx, _ = router_probs(xt, router, k)

    # ---- build fixed-capacity send buffer (E, C, D) ----
    C = max(1, int(T * k * capacity_factor) // E)
    flat_e = idx.reshape(-1)                                 # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_g = gates.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position of each element within its expert segment
    start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos = jnp.arange(T * k) - start[se]
    keep = pos < C
    slot_e = jnp.where(keep, se, 0)
    slot_c = jnp.where(keep, pos, 0)
    send = jnp.zeros((E, C, xt.shape[1]), xt.dtype)
    send = send.at[slot_e, slot_c].add(
        jnp.where(keep[:, None], xt[st], 0.0).astype(xt.dtype))

    # ---- A2A to expert shards (tiled, split==concat: self-transpose, so
    # the VJP is the same collective — no cotangent-layout ambiguity) ----
    # out[j*E_loc + e_loc] = device j's buffer for MY local experts
    out = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0,
                             tiled=True)
    recv = out.reshape(n_model, E_loc, C, -1).transpose(1, 0, 2, 3)
    recv = recv.reshape(E_loc, n_model * C, -1)

    # ---- expert FFNs ----
    y = _expert_ffn(recv, wi, wo)

    # ---- A2A back: rows regrouped so chunk j = outputs for device j ----
    y = y.reshape(E_loc, n_model, C, -1).transpose(1, 0, 2, 3)
    back = jax.lax.all_to_all(y.reshape(E, C, -1), model_axis,
                              split_axis=0, concat_axis=0, tiled=True)
    # back[e] now holds the processed send[e] (e = owner*E_loc + e_loc)

    # ---- weighted combine ----
    contrib = back[slot_e, slot_c]                           # (T*k, D)
    contrib = jnp.where(keep[:, None], contrib, 0.0)
    out = jnp.zeros_like(xt, dtype=jnp.float32)
    out = out.at[st].add(contrib.astype(jnp.float32) * sg[:, None])
    out = out.astype(xt_full.dtype)
    if T != T_full:
        # gather every rank's token slice back into the full (replicated)
        # activation: (n_model, T, D) -> (T_full, D), slices contiguous
        out = jax.lax.all_gather(out, model_axis, axis=0).reshape(
            T_full, -1)
    return out


def moe_ep(x: jax.Array, params: dict, k: int, n_experts: int,
           capacity_factor: float, ctx: ModelContext) -> jax.Array:
    """Expert-parallel MoE via shard_map over the full mesh."""
    assert ctx.distributed, "EP MoE requires a mesh"
    mesh = ctx.mesh
    n_model = mesh.shape["model"]
    B, S, D = x.shape
    batch_axes = ctx.rules["batch"]
    replicated = "model" not in ((batch_axes,) if isinstance(
        batch_axes, str) else (batch_axes or ()))
    x_spec = P(batch_axes, None, None)

    def body(xb, router, wi, wo):
        T_loc = xb.shape[0] * xb.shape[1]
        out = _ep_local(xb.reshape(T_loc, D), router, wi, wo,
                        k=k, n_experts=n_experts,
                        capacity_factor=capacity_factor,
                        model_axis="model", n_model=n_model,
                        tokens_replicated=replicated)
        return out.reshape(xb.shape)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=x_spec,
        check_vma=False)
    return fn(x, params["router"], params["wi"], params["wo"])


def moe_block(x: jax.Array, params: dict, *, k: int, n_experts: int,
              n_shared: int, capacity_factor: float,
              ctx: Optional[ModelContext] = None) -> jax.Array:
    """Routed experts + optional shared experts (Moonlight-style)."""
    impl = ctx.moe_impl if ctx is not None else "dense"
    if impl == "auto":
        impl = "ep" if (ctx is not None and ctx.distributed) else "dense"
    if impl == "ep":
        y = moe_ep(x, params, k, n_experts, capacity_factor, ctx)
    else:
        y = moe_dense(x, params, k, ctx)
    if n_shared > 0:
        y = y + swiglu(x, params["wi_s"], params["wo_s"], ctx)
    return y
