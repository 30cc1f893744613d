"""Plain fp32 reference of GTE-ModernColBERT-v1's forward pass.

ModernBERT-base (Warner et al., arXiv:2412.13663) under PyLate's ColBERT
head, written out as the equations read, in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: a Python loop over the
layers, one dense (S, S) mask per layer, no scan, no kernels, no
batching tricks.

    h = LayerNorm(E[ids])
    layer i:  h = h + Wo_i Attn_i(Norm_attn_i(h))      (Norm_attn_0 = Identity)
              h = h + Wo_mlp_i (GELU(a) * g),  [a, g] = split(Wi_i Norm_mlp_i(h))
    out = normalize(LayerNorm_final(h) @ P)

Every LayerNorm is bias-free with eps 1e-5, GELU is the exact (erf)
form, no linear layer has a bias.  Layer i is global when ``i %
global_every == 0``: full bidirectional attention, RoPE base
``rope_theta``; otherwise local: query i sees key j only if ``|i - j| <=
local_window // 2``, RoPE base ``local_rope_theta``.  RoPE is the
rotate-half form.  Keys outside the attention mask are never attended.

Departures, each shared with the program: the weights are read from the
program's parameter pytree (layer 0 under ``layer0``, layers 1.. stacked
as (periods, global_every, ...)), since both must use the same seeded
weights; a key that a row may not see scores -1e30 rather than -inf, so a
padded row that sees no key averages all of them instead of giving NaN
(only real rows are ever compared); the ids 0 (pad), 3 ([MASK]) and the
marker first tokens are the repo's reserved ids, not ModernBERT's
tokenizer's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
NEG = -1e30
MASK_ID = 3


def layer_params(backbone: dict, i: int, global_every: int) -> dict:
    """Layer ``i``'s weights from the program's parameter pytree."""
    if i == 0:
        return backbone["layer0"]
    p, k = divmod(i - 1, global_every)
    return jax.tree_util.tree_map(lambda a: a[p, k], backbone["layers"])


def layer_norm(x, gamma):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * gamma


def rope(x, theta: float):
    """Rotate-half RoPE of x: (n, S, heads, hd) at positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp: dict, attend, *, n_heads: int, theta: float,
              band: int | None):
    n, s, d = x.shape
    hd = d // n_heads
    a = lp["attn"]
    q = rope((x @ a["wq"]).reshape(n, s, n_heads, hd), theta)
    k = rope((x @ a["wk"]).reshape(n, s, n_heads, hd), theta)
    v = (x @ a["wv"]).reshape(n, s, n_heads, hd)
    scores = jnp.einsum("nihd,njhd->nhij", q, k) / np.sqrt(hd)
    i = np.arange(s)
    visible = np.ones((s, s), bool)
    if band is not None:
        visible = np.abs(i[:, None] - i[None, :]) <= band
    visible = jnp.asarray(visible)[None, None] & attend[:, None, None, :]
    w = jax.nn.softmax(jnp.where(visible, scores, NEG), axis=-1)
    ctx = jnp.einsum("nhij,njhd->nihd", w, v).reshape(n, s, d)
    return ctx @ a["wo"]


def hidden_states(params: dict, cfg, ids, attend):
    """Final hidden states (n, S, d_model) of token ids under the key
    mask ``attend``, both (n, S)."""
    bb = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                params["backbone"])
    attend = jnp.asarray(attend, bool)
    with jax.default_matmul_precision("highest"):
        h = layer_norm(bb["embed"][jnp.asarray(ids)], bb["embed_norm"])
        for i in range(cfg.n_layers):
            lp = layer_params(bb, i, cfg.global_every)
            local = i % cfg.global_every != 0
            x = h if i == 0 else layer_norm(h, lp["ln1"])
            h = h + attention(
                x, lp, attend, n_heads=cfg.n_heads,
                theta=cfg.local_rope_theta if local else cfg.rope_theta,
                band=cfg.local_window // 2 if local else None)
            a, g = jnp.split(layer_norm(h, lp["ln2"]) @ lp["ffn"]["wi"], 2,
                             axis=-1)
            h = h + (jax.nn.gelu(a, approximate=False) * g) @ lp["ffn"]["wo"]
        return layer_norm(h, bb["ln_f"])


def embed(params: dict, cfg, ids, attend):
    """Unit-sphere token embeddings (n, S, out_dim) in fp32."""
    h = hidden_states(params, cfg, ids, attend)
    with jax.default_matmul_precision("highest"):
        raw = h @ jnp.asarray(params["proj"], jnp.float32)
    return raw / jnp.maximum(jnp.linalg.norm(raw, axis=-1, keepdims=True),
                             1e-9)


def encode_docs(params: dict, cfg, ids):
    """Documents: pad (id 0) is neither attended to nor kept."""
    mask = np.asarray(ids) != 0
    return embed(params, cfg, ids, mask), mask


def encode_queries(params: dict, cfg, ids):
    """Queries: padded to ``query_len`` with [MASK], every position
    embedded; the expansion tokens are not attended to (PyLate's
    ``attend_to_expansion_tokens`` false)."""
    ids = np.asarray(ids)[:, :cfg.query_len]
    ids = np.pad(ids, ((0, 0), (0, cfg.query_len - ids.shape[1])))
    attend = (ids != 0) & (ids != MASK_ID)
    ids = np.where(ids == 0, MASK_ID, ids)
    return embed(params, cfg, ids, attend), np.ones(ids.shape, bool)
