"""Top-k MoE with capacity-based sort dispatch (GShard-style semantics,
argsort-based implementation so the dispatch tensor stays O(tokens), not
O(tokens * experts * capacity)).

Expert-parallel sharding: the (E, C, d) expert buffers carry the "experts"
logical axis, which maps to the "data" mesh axis when divisible (arctic:
128 experts over 16 -> 8/slice); expert FFN hidden dims carry "ffn" ->
"model" (TP inside experts).  GSPMD inserts the token all-to-alls at the
sharding boundaries.

The dense-residual branch (arctic) is a plain MLP added in parallel.
Auxiliary load-balancing loss follows Switch/GShard: E * sum(f_e * p_e).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_mlp, dense_init, init_mlp


def init_moe(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p, s = {}, {}
    p["router"], s["router"] = dense_init(ks[0], d, E, "embed", None, dtype)

    def expert_stack(k, d_in, d_out):
        w = jax.random.normal(k, (E, d_in, d_out), jnp.float32) * (d_in ** -0.5)
        return w.astype(dtype)

    p["gate"] = expert_stack(ks[1], d, f)
    s["gate"] = ("experts", "embed", "ffn")
    p["up"] = expert_stack(ks[2], d, f)
    s["up"] = ("experts", "embed", "ffn")
    p["down"] = expert_stack(ks[3], f, d)
    s["down"] = ("experts", "ffn", "embed")
    if cfg.dense_residual_d_ff:
        p["dense"], s["dense"] = init_mlp(
            ks[4], d, cfg.dense_residual_d_ff, cfg.mlp_type, dtype
        )
    return p, s


def apply_moe(p, x: jax.Array, cfg: ModelConfig):
    """x: (B, S, d). Returns (out, aux_loss)."""
    if cfg.moe_impl == "shard_map":
        from repro.parallel.sharding import current_mesh

        mesh = current_mesh()
        if mesh is not None:
            data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            n = 1
            for a in data_axes:
                n *= mesh.shape[a]
            if data_axes and cfg.n_experts % n == 0 and x.shape[0] % n == 0:
                return apply_moe_shard_map(p, x, cfg, mesh, data_axes)
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    xf = x.reshape(T, d)

    logits = (xf @ p["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # --- aux load-balance loss (Switch eq. 4) ---
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0
    )
    aux = E * jnp.sum(me * ce)

    # --- sort-based dispatch with capacity ---
    # capped at T*k (beyond that capacity is unreachable); setting
    # capacity_factor >= n_experts therefore yields dropless routing.
    C = int(min(T * k, max(1, round(k * T * cfg.capacity_factor / E))))
    flat_expert = expert_idx.reshape(-1)  # (T*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), k)

    order = jnp.argsort(flat_expert, stable=True)
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    # position within the expert group
    same = jax.nn.one_hot(se, E, dtype=jnp.int32)  # (T*k, E)
    pos_in_e = (jnp.cumsum(same, axis=0) - same)[jnp.arange(T * k), se]
    keep = pos_in_e < C
    dst = jnp.where(keep, se * C + pos_in_e, 0)  # dropped -> slot 0, masked

    # masked scatter-add keeps the (E*C, d) buffer shape shardable over the
    # expert axis (an extra scratch row would block SPMD partitioning).
    contrib = jnp.where(keep[:, None], xf[st], 0)
    buf = jnp.zeros((E * C, d), xf.dtype).at[dst].add(contrib)
    buf = buf.reshape(E, C, d)
    buf = _constrain_expert(buf)

    # --- expert computation (swiglu) ---
    if cfg.mlp_type == "geglu":
        act = lambda g: jax.nn.gelu(g, approximate=True)
    else:
        act = jax.nn.silu
    h = act(jnp.einsum("ecd,edf->ecf", buf, p["gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["up"]
    )
    y = jnp.einsum("ecf,efd->ecd", h, p["down"])
    y = _constrain_expert(y)

    # --- combine ---
    y_flat = y.reshape(E * C, d)
    gathered = y_flat[dst] * (sg * keep).astype(y.dtype)[:, None]
    out = jax.ops.segment_sum(gathered, st, num_segments=T)

    if cfg.dense_residual_d_ff:
        out = out + apply_mlp(p["dense"], xf, cfg.mlp_type)
    return out.reshape(B, S, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel path (explicit all-to-all)
# ---------------------------------------------------------------------------
#
# The GSPMD path above lets XLA re-shard the (E*C, d) dispatch buffers, which
# it lowers to scatter + full all-reduces — ~40x the algorithmic-minimum
# network volume for arctic-class models (see EXPERIMENTS.md §Perf).  This
# path routes tokens with two explicit all_to_alls over the "data" axis
# (expert parallelism), the schedule every production MoE framework uses.
# Requires n_experts % data_axis == 0; apply_moe() dispatches automatically.


def _moe_local_route(xf, p, cfg, E, k, n_shards):
    """Local routing on this shard's tokens: returns send buffer + indices."""
    T_loc, d = xf.shape
    logits = (xf @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    # per-(expert) capacity of tokens sent from THIS shard
    C = int(max(1, round(k * T_loc * cfg.capacity_factor / E)))
    C = min(C, T_loc * k)
    flat_e = expert_idx.reshape(-1)
    flat_g = gate_vals.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T_loc), k)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    same = jax.nn.one_hot(se, E, dtype=jnp.int32)
    pos = (jnp.cumsum(same, axis=0) - same)[jnp.arange(T_loc * k), se]
    keep = pos < C
    dst = jnp.where(keep, se * C + pos, 0)
    contrib = jnp.where(keep[:, None], xf[st], 0)
    send = jnp.zeros((E * C, d), xf.dtype).at[dst].add(contrib)
    return send.reshape(E, C, d), (dst, st, sg, keep, C), aux


def apply_moe_shard_map(p, x: jax.Array, cfg: ModelConfig, mesh, data_axes):
    """Expert-parallel MoE via explicit all_to_all over ``data_axes``,
    composed with tensor parallelism over "model" inside each expert
    (partial-sum down-projection + reduce-scatter epilogue)."""
    from jax.sharding import PartitionSpec as P

    from jax.shard_map import shard_map

    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    E_loc = E // n_shards
    axis = data_axes if len(data_axes) > 1 else data_axes[0]
    has_model = "model" in mesh.axis_names
    mp = mesh.shape["model"] if has_model else 1
    scatter_seq = has_model and mp > 1 and S % mp == 0

    def local_fn(xl, router, gate_w, up_w, down_w):
        # xl: (B_loc, S, d) replicated over "model"; expert weights arrive
        # (E_loc, d, f/mp) — expert-sharded over data, TP-sharded over model.
        Bl = xl.shape[0]
        xf = xl.reshape(Bl * S, d)
        send, (dst, st, sg, keep, C), aux = _moe_local_route(
            xf, {"router": router}, cfg, E, k, n_shards
        )
        send = send.reshape(n_shards, E_loc, C, d)
        recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
        buf = jnp.moveaxis(recv, 0, 1).reshape(E_loc, n_shards * C, d)
        act = (
            (lambda g: jax.nn.gelu(g, approximate=True))
            if cfg.mlp_type == "geglu"
            else jax.nn.silu
        )
        h = act(jnp.einsum("ecd,edf->ecf", buf, gate_w)) * jnp.einsum(
            "ecd,edf->ecf", buf, up_w
        )
        y = jnp.einsum("ecf,efd->ecd", h, down_w)  # partial sum over f-shards
        # reverse route the PARTIAL sums (linear), combine locally, then one
        # reduce-scatter finishes the TP reduction with seq-sharded output.
        y = jnp.moveaxis(y.reshape(E_loc, n_shards, C, d), 1, 0)
        back = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0)
        y_flat = back.reshape(E * C, d)
        gathered = y_flat[dst] * (sg * keep).astype(y_flat.dtype)[:, None]
        out = jax.ops.segment_sum(gathered, st, num_segments=Bl * S)
        out = out.reshape(Bl, S, d)
        if scatter_seq:
            out = jax.lax.psum_scatter(out, "model", scatter_dimension=1, tiled=True)
        elif has_model and mp > 1:
            out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, axis)
        return out.astype(xl.dtype), aux

    bspec = P(axis, None, None)
    espec = P(axis, None, "model" if has_model else None)
    out_spec = P(axis, "model", None) if scatter_seq else bspec
    out, aux = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(bspec, P(None, None), espec, espec, P(axis, "model" if has_model else None, None)),
        out_specs=(out_spec, P()),
        check_rep=False,
    )(x, p["router"], p["gate"], p["up"], p["down"])
    if cfg.dense_residual_d_ff:
        out = out + apply_mlp(p["dense"], x, cfg.mlp_type)
    return out, aux


def _constrain_expert(t: jax.Array) -> jax.Array:
    """Hook for expert-parallel sharding constraints; the parallel layer
    monkey-wires this at trace time (keeps models mesh-agnostic)."""
    return _EXPERT_CONSTRAINT(t)


def _identity(t):
    return t


_EXPERT_CONSTRAINT = _identity


def set_expert_constraint(fn):
    global _EXPERT_CONSTRAINT
    _EXPERT_CONSTRAINT = fn if fn is not None else _identity
