"""Shared transformer layers — functional, params-as-pytrees, spec-parallel.

Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the
params pytree with ``jax.sharding.PartitionSpec`` leaves (Megatron-style TP
over the ``model`` mesh axis; optional FSDP sharding of the remaining dim
over ``data`` for the very large archs).

Compute follows the usual mixed-precision discipline: params in
``cfg.param_dtype`` (f32 small / bf16 huge), activations in
``cfg.compute_dtype`` (bf16), reductions (softmax, norms) in f32.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that is a no-op when no mesh is in context
    (single-host tests / CPU examples) or the spec names absent axes."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    flat = []
    for e in tuple(spec):
        for a in (e if isinstance(e, tuple) else (e,)):
            if a is not None:
                flat.append(a)
    if any(a not in mesh.axis_names for a in flat):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float = 1.0):
    fan_in = shape[0] if len(shape) > 1 else 1
    std = scale / (fan_in ** 0.5)
    return (jax.random.normal(key, shape) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> Tuple[dict, dict]:
    return {"scale": jnp.ones((d,), dtype)}, {"scale": P(None)}


def rmsnorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype) -> Tuple[dict, dict]:
    return (
        {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)},
        {"scale": P(None), "bias": P(None)},
    )


def layernorm(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) int32 absolute positions."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)                                   # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs          # (B, S, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]                               # (B, S, 1, Dh/2)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, chunked over queries so S x S never materializes)
# ---------------------------------------------------------------------------

def attention(
    q: jax.Array,                    # (B, Sq, H, Dh)
    k: jax.Array,                    # (B, Sk, Hkv, Dh)
    v: jax.Array,                    # (B, Sk, Hkv, Dhv)
    *,
    causal: bool = True,
    q_offset=0,                      # scalar or (B,): absolute pos of q[:, 0]
    kv_len: Optional[jax.Array] = None,  # (B,) valid kv prefix (decode/serve)
    chunk: Optional[int] = None,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention with query chunking.

    Scores for one query chunk are (B, Hkv, G, Cq, Sk) — the full (Sq, Sk)
    score matrix never exists, which is what lets the 32k-prefill cells
    compile inside HBM.  Softmax in f32.
    """
    b, sq, h, dh = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    scale = softmax_scale if softmax_scale is not None else dh ** -0.5
    qg = q.reshape(b, sq, hkv, g, dh)

    kv_pos = jnp.arange(sk)
    off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (b,))

    def block(qc: jax.Array, rel: jax.Array) -> jax.Array:
        # qc: (B, Cq, Hkv, G, Dh); rel: (Cq,) chunk-relative positions
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qc.astype(jnp.float32), k.astype(jnp.float32))
        s = s * scale
        q_pos = off[:, None] + rel[None, :]                       # (B, Cq)
        mask = jnp.ones((b, qc.shape[1], sk), dtype=bool)
        if causal:
            mask = kv_pos[None, None, :] <= q_pos[:, :, None]
        if kv_len is not None:
            mask = jnp.logical_and(mask, (kv_pos[None, :] < kv_len[:, None])[:, None, :])
        s = jnp.where(mask[:, None, None, :, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
        return o.astype(q.dtype)

    if chunk is None or chunk >= sq:
        out = block(qg, jnp.arange(sq))
        return out.reshape(b, sq, h, v.shape[-1])

    pad = (-sq) % chunk
    if pad:                              # ragged tail: pad queries, slice out
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    nchunk = (sq + pad) // chunk
    qs = qg.reshape(b, nchunk, chunk, hkv, g, dh)

    # checkpoint each chunk: without it the scan saves every chunk's f32
    # scores/probs as backward residuals — the full O(S^2) tensor the
    # chunking exists to avoid.  Recomputing scores in the backward keeps
    # attention memory O(S * chunk) at ~1.3x attention flops.
    blk = jax.checkpoint(block, policy=jax.checkpoint_policies.nothing_saveable)

    def body(i):
        return blk(qs[:, i], i * chunk + jnp.arange(chunk))

    out = jax.lax.map(body, jnp.arange(nchunk))                   # (n, B, C, ...)
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq + pad, h, v.shape[-1])
    return out[:, :sq]


# ---------------------------------------------------------------------------
# GQA projection block
# ---------------------------------------------------------------------------

def init_gqa(key, cfg) -> Tuple[dict, dict]:
    dh = cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    dt = cfg.param_dtype
    p = {
        "wq": dense_init(kq, (cfg.d_model, cfg.n_heads * dh), dt),
        "wk": dense_init(kk, (cfg.d_model, cfg.n_kv_heads * dh), dt),
        "wv": dense_init(kv, (cfg.d_model, cfg.n_kv_heads * dh), dt),
        "wo": dense_init(ko, (cfg.n_heads * dh, cfg.d_model), dt),
    }
    fsdp = "data" if getattr(cfg, "fsdp_params", False) else None
    s = {
        "wq": P(fsdp, "model"),
        "wk": P(fsdp, "model"),
        "wv": P(fsdp, "model"),
        "wo": P("model", fsdp),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * dh,), dt)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * dh,), dt)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * dh,), dt)
        s["bq"] = P("model")
        s["bk"] = P("model")
        s["bv"] = P("model")
    return p, s


def gqa_qkv(p: dict, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    dh = cfg.head_dim
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    return (
        q.reshape(b, s, cfg.n_heads, dh),
        k.reshape(b, s, cfg.n_kv_heads, dh),
        v.reshape(b, s, cfg.n_kv_heads, dh),
    )


def gqa_out(p: dict, o: jax.Array) -> jax.Array:
    b, s, h, dh = o.shape
    return o.reshape(b, s, h * dh) @ p["wo"].astype(o.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype, fsdp: bool = False) -> Tuple[dict, dict]:
    kg, ku, kd = jax.random.split(key, 3)
    p = {
        "wg": dense_init(kg, (d_model, d_ff), dtype),
        "wu": dense_init(ku, (d_model, d_ff), dtype),
        "wd": dense_init(kd, (d_ff, d_model), dtype),
    }
    f = "data" if fsdp else None
    s = {"wg": P(f, "model"), "wu": P(f, "model"), "wd": P("model", f)}
    return p, s


def swiglu(p: dict, x: jax.Array) -> jax.Array:
    g = jax.nn.silu(x @ p["wg"].astype(x.dtype))
    u = x @ p["wu"].astype(x.dtype)
    return (g * u) @ p["wd"].astype(x.dtype)


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(key, vocab: int, d_model: int, dtype) -> Tuple[dict, dict]:
    return (
        {"table": embed_init(key, (vocab, d_model), dtype)},
        {"table": P("model", None)},
    )


def embed(p: dict, tokens: jax.Array, compute_dtype) -> jax.Array:
    return p["table"].astype(compute_dtype)[tokens]


def unembed(p: dict, x: jax.Array) -> jax.Array:
    """Logits in f32 (loss stability); vocab dim sharded over model."""
    return x.astype(jnp.float32) @ p["table"].astype(jnp.float32).T
