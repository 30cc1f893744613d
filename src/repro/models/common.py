"""Shared neural building blocks (pure-function style, pytree params)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding import constrain


def dense_init(key, in_dim, out_dim, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(in_dim))
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab, dim, dtype=jnp.float32, scale=0.02):
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * scale
            ).astype(dtype)


def rms_norm(x, gamma, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(dt)


def layer_norm(x, gamma, beta=None, eps=1e-6):
    """LayerNorm over the last axis, in fp32; ``beta=None`` is the
    bias-free form."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    if beta is not None:
        y = y + beta.astype(jnp.float32)
    return y.astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """Rotary position embedding. x: (..., seq, heads, head_dim)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def geglu(x, w_in, w_out):
    """GeGLU feed-forward: ``[a, g] = split(x @ w_in)``, then
    ``(GELU(a) * g) @ w_out``, with the exact (erf) GELU."""
    a, g = jnp.split(x @ w_in, 2, axis=-1)
    h = jax.nn.gelu(a, approximate=False) * g
    h = constrain(h, "batch", "seq", "ffn")
    return h @ w_out


def swiglu(x, w_gate, w_up, w_down, b_gate=None, b_up=None, b_down=None):
    g = x @ w_gate
    u = x @ w_up
    if b_gate is not None:
        g = g + b_gate
    if b_up is not None:
        u = u + b_up
    h = jax.nn.silu(g) * u
    h = constrain(h, "batch", "seq", "ffn")
    y = h @ w_down
    if b_down is not None:
        y = y + b_down
    return y


def mlp(x, ws, bs=None, act=jax.nn.relu, final_act=False):
    """Plain MLP over last axis; ws list of (in,out) weights."""
    h = x
    for i, w in enumerate(ws):
        h = h @ w
        if bs is not None and bs[i] is not None:
            h = h + bs[i]
        if i < len(ws) - 1 or final_act:
            h = act(h)
    return h


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params)
               if hasattr(x, "size"))


def cast_tree(params, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, params)
