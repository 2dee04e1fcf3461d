"""Core transformer building blocks (pure functions, sharding-annotated)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.common import ArchConfig, MeshAxes, constrain


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * scale


def rope(q, positions, theta, dtype=None):
    """Rotary embedding over the last dim of (..., S, H, dh)."""
    dh = q.shape[-1]
    half = dh // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    q1, q2 = q[..., :half].astype(jnp.float32), q[..., half:].astype(jnp.float32)
    out = jnp.concatenate([q1 * cos - q2 * sin, q1 * sin + q2 * cos], axis=-1)
    return out.astype(dtype or q.dtype)


def attention(
    cfg: ArchConfig,
    mesh: Mesh,
    axes: MeshAxes,
    q,                      # (B, Sq, H, dh)
    k,                      # (B, Sk, KV, dh)
    v,                      # (B, Sk, KV, dh)
    mask,                   # broadcastable to (B, H, Sq, Sk) bool, or None
    mask_kind: str | None = None,   # "causal" | "prefix:<n>" | None — enables
                                    # the chunked path without an S×S mask
):
    """GQA attention with soft TP over heads (uneven OK via GSPMD padding),
    or query-position sharding over "model" (attn_seq_shard — §Perf)."""
    b_axes = axes.batch
    if cfg.attn_seq_shard and q.shape[1] % max(axes.size(axes.model), 1) == 0:
        # shard queries (not heads) over "model": no head-padding waste and
        # no seq<->head reshards against the seq-parallel residual stream
        h_tp = None
        q = constrain(q, mesh, b_axes, axes.model, None, None)
        k = constrain(k, mesh, b_axes, None, None, None)
        v = constrain(v, mesh, b_axes, None, None, None)
    else:
        h_tp = axes.model  # soft constraint — GSPMD pads when H % tp != 0
        q = constrain(q, mesh, b_axes, None, h_tp, None)
    rep = cfg.n_heads // cfg.n_kv_heads
    if rep > 1:
        if cfg.gqa_shard_fix:
            # gather the sequence dim and pin KV to the head-TP layout BEFORE
            # the repeat: without this GSPMD reshards (seq-sharded -> uneven
            # head-sharded) through an involuntary full rematerialization
            k = constrain(k, mesh, b_axes, None, None, None)
            v = constrain(v, mesh, b_axes, None, None, None)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        if cfg.gqa_shard_fix:
            k = constrain(k, mesh, b_axes, None, h_tp, None)
            v = constrain(v, mesh, b_axes, None, h_tp, None)
    if cfg.attn_chunk and q.shape[1] > 1 and k.shape[1] > cfg.attn_chunk:
        return _chunked_attention(cfg, mesh, axes, q, k, v, mask_kind or "full", h_tp)
    scale = cfg.head_dim ** -0.5
    logits = jnp.einsum("bqhe,bkhe->bhqk", q, k) * scale
    logits = constrain(logits, mesh, b_axes, h_tp, None, None)
    if cfg.attn_logits_f32:
        logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhe->bqhe", probs, v)
    return constrain(out, mesh, b_axes, None, h_tp, None)


def _chunked_attention(cfg: ArchConfig, mesh: Mesh, axes: MeshAxes, q, k, v, mask_kind: str,
                       h_tp=None):
    """Online-softmax attention over KV chunks (flash-style at HLO level).

    The (Sq, Sk) score matrix never materializes in HBM as a whole: each
    scan step touches a (Sq, C) tile once, cutting the ~6 full-matrix HBM
    passes of the naive path (einsum, mask, fp32 convert, softmax, cast,
    PV read) to ~2 tile passes.  The per-chunk mask is computed from
    positions, so no S×S bool mask exists either.
    """
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    c = cfg.attn_chunk
    nc = sk // c
    assert sk % c == 0, (sk, c)
    b_axes = axes.batch
    q_seq = axes.model if (cfg.attn_seq_shard and h_tp is None) else None
    scale = dh ** -0.5
    prefix_len = int(mask_kind.split(":")[1]) if mask_kind.startswith("prefix") else 0
    q_pos = jnp.arange(sq)

    kc = k.reshape(b, nc, c, h, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, c, h, dh).transpose(1, 0, 2, 3, 4)

    def body(carry, inputs):
        m, l, acc = carry
        ci, k_i, v_i = inputs
        s = jnp.einsum("bqhe,bkhe->bhqk", q, k_i).astype(jnp.float32) * scale
        s = jax.lax.with_sharding_constraint(
            s, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(b_axes, h_tp, q_seq, None))
        )
        k_pos = ci * c + jnp.arange(c)
        if mask_kind == "causal":
            msk = k_pos[None, :] <= q_pos[:, None]
        elif prefix_len:
            msk = (k_pos[None, :] <= q_pos[:, None]) | (k_pos[None, :] < prefix_len)
        else:
            msk = None
        if msk is not None:
            s = jnp.where(msk[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # all--inf rows (fully masked chunk) keep m = -inf; guard the exps
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhe->bqhe", p.astype(q.dtype), v_i).astype(jnp.float32)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, sq, h, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (jnp.arange(nc), kc, vc))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return constrain(out.astype(q.dtype), mesh, b_axes, q_seq, h_tp, None)


def causal_mask(s: int):
    return jnp.tril(jnp.ones((s, s), dtype=bool))[None, None]


def prefix_lm_mask(s: int, prefix_len: int):
    """Bidirectional over the first ``prefix_len`` positions, causal after
    (PaliGemma-style image-prefix attention)."""
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    prefix = (jnp.arange(s)[None, :] < prefix_len) & (jnp.arange(s)[:, None] >= 0)
    return (causal | prefix)[None, None]


def mlp_block(cfg: ArchConfig, mesh: Mesh, axes: MeshAxes, x, p):
    b_axes = axes.batch
    f_tp = axes.tp(cfg.d_ff)
    if cfg.mlp == "swiglu":
        g = constrain(jnp.einsum("bsd,df->bsf", x, p["wg"]), mesh, b_axes, None, f_tp)
        u = constrain(jnp.einsum("bsd,df->bsf", x, p["wu"]), mesh, b_axes, None, f_tp)
        h = jax.nn.silu(g) * u
    else:  # gelu
        h = constrain(jnp.einsum("bsd,df->bsf", x, p["wu"]), mesh, b_axes, None, f_tp)
        h = jax.nn.gelu(h)
    return row_parallel_out(cfg, mesh, axes, h, p["wd"], "bsf,fd->bsd", f_tp)


def row_parallel_out(cfg: ArchConfig, mesh: Mesh, axes: MeshAxes, h, w, eq, contr_tp):
    """Row-parallel output projection.  With dense_scatter_combine the partial
    products reduce-scatter straight into the seq-sharded residual layout
    (half the bytes of all-reduce + slice) — §Perf lever."""
    ok = (
        cfg.dense_scatter_combine
        and cfg.seq_parallel
        and contr_tp is not None
        and axes.model
        and h.shape[1] % axes.size(axes.model) == 0
        and h.ndim == 3
    )
    if not ok:
        return jnp.einsum(eq, h, w)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(hh, ww):
        part = jnp.einsum(eq, hh, ww)
        return jax.lax.psum_scatter(part, axes.model, scatter_dimension=1, tiled=True)

    f = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes.batch, None, axes.model), P(axes.model, None)),
        out_specs=P(axes.batch, axes.model, None),
        check_vma=False,
    )
    return f(h, w)


def qkv(cfg: ArchConfig, x, p, positions):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v
