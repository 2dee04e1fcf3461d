"""Mixture-of-Experts FFN with expert parallelism over the "model" axis.

DESIGN.md §Arch-applicability: this layer is the framework's closest analogue
of the paper's node-aware blocked communication.  Because activations are
TP-*replicated* across the "model" axis (they are only batch/seq-sharded),
every expert shard already holds the tokens it may need — so the usual
all-to-all *dispatch* is a purely local capacity-gather, and the only
collective is a single psum *combine* (the same collective a dense
row-parallel MLP needs).  Duplicated slow-tier traffic is traded for local
work: the 2-step/3-step philosophy applied to MoE routing.

Routing is top-k with per-device capacity  C = ceil(T_loc·k/E · cf)
(tokens over capacity are dropped — standard Switch/GShard semantics,
deterministic and static-shaped).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.models.common import ArchConfig, MeshAxes


def moe_ffn(cfg: ArchConfig, mesh: Mesh, axes: MeshAxes, x, p):
    """x: (B, S, D) batch-sharded; p: router (D,E), we_g/we_u (E,D,F), we_d (E,F,D)
    with E sharded over "model".  Returns (B, S, D)."""
    model_axis = axes.model
    e_shards = axes.size(model_axis)
    assert cfg.n_experts % max(e_shards, 1) == 0, "experts must divide model axis"
    b, s, d = x.shape

    scatter = bool(cfg.moe_scatter_combine and model_axis and s % e_shards == 0)
    in_specs = (
        P(axes.batch, None, None),            # x (replicated over model)
        P(None, None),                        # router (replicated)
        P(model_axis, None, None),            # we_g
        P(model_axis, None, None),            # we_u
        P(model_axis, None, None),            # we_d
    )
    # scatter-combine emits the output already sequence-sharded over "model"
    # (reduce-scatter = half the bytes of all-reduce) — §Perf lever
    out_x = P(axes.batch, model_axis, None) if scatter else P(axes.batch, None, None)
    out_specs = (out_x, P())

    f = shard_map(
        functools.partial(_moe_local, cfg, e_shards, model_axis, tuple(axes.batch), scatter),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    out, aux = f(x, p["router"], p["we_g"], p["we_u"], p["we_d"])
    return out, aux


def _moe_local(cfg, e_shards, model_axis, batch_axes, scatter, x, router, wg, wu, wd):
    """Per-device body: local top-k routing + capacity gather + local experts
    + weighted scatter + psum combine."""
    bl, s, d = x.shape
    t_loc = bl * s
    e_total = cfg.n_experts
    e_loc = e_total // e_shards
    k = cfg.top_k
    cap = int(max(1, -(-t_loc * k // e_total) * cfg.capacity_factor))

    xf = x.reshape(t_loc, d)
    gate_logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router.astype(jnp.float32))
    probs = jax.nn.softmax(gate_logits, axis=-1)          # (T, E)
    top_vals, top_ids = jax.lax.top_k(probs, k)           # (T, k)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # which experts this shard owns
    shard_id = jax.lax.axis_index(model_axis) if model_axis else 0
    e0 = shard_id * e_loc

    def one_expert(e_local, carry):
        e = e0 + e_local
        match = top_ids == e                              # (T, k)
        gate_e = jnp.sum(jnp.where(match, top_vals, 0.0), axis=-1)  # (T,)
        mem = jnp.any(match, axis=-1)                     # (T,)
        rank = jnp.cumsum(mem) - 1
        sel = mem & (rank < cap)
        order = jnp.argsort(~sel, stable=True)[:cap]      # selected first
        valid = sel[order]
        g = jnp.where(valid, gate_e[order], 0.0)          # (cap,)
        xe = xf[order]                                    # (cap, d)
        if cfg.mlp == "swiglu":
            h = jax.nn.silu(xe @ wg[e_local]) * (xe @ wu[e_local])
        else:
            h = jax.nn.gelu(xe @ wu[e_local])
        ye = (h @ wd[e_local]) * g[:, None].astype(x.dtype)
        return carry.at[order].add(ye)

    out = jnp.zeros_like(xf)
    for e_local in range(e_loc):
        out = one_expert(e_local, out)

    # combine across expert shards — ONE collective (cf. module docstring)
    if model_axis and scatter:
        out = out.reshape(bl, s, d)
        out = jax.lax.psum_scatter(out, model_axis, scatter_dimension=1, tiled=True)
        out = out.reshape(-1, d)
    elif model_axis:
        out = jax.lax.psum(out, model_axis)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e, reduced globally
    density = jnp.mean(
        jax.nn.one_hot(top_ids, e_total, dtype=jnp.float32).sum(axis=1), axis=0
    )
    mean_probs = jnp.mean(probs, axis=0)
    aux = e_total * jnp.sum(density * mean_probs)
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    if scatter:
        return out.reshape(bl, s // e_shards, d), aux
    return out.reshape(bl, s, d), aux


def moe_ffn_reference(cfg: ArchConfig, x, p):
    """Dense (no-drop) oracle for tests: every token sees its top-k experts."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_ids = jax.lax.top_k(probs, cfg.top_k)
    top_vals = top_vals / top_vals.sum(-1, keepdims=True)
    out = jnp.zeros_like(xf)
    for e in range(cfg.n_experts):
        if cfg.mlp == "swiglu":
            h = jax.nn.silu(xf @ p["we_g"][e]) * (xf @ p["we_u"][e])
        else:
            h = jax.nn.gelu(xf @ p["we_u"][e])
        ye = h @ p["we_d"][e]
        gate = jnp.sum(jnp.where(top_ids == e, top_vals, 0.0), axis=-1)
        out = out + ye * gate[:, None].astype(x.dtype)
    return out.reshape(b, s, d)
