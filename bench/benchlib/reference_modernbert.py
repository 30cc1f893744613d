"""Plain reference of GTE-ModernColBERT-v1's document encoder, the
benchmark's own copy: ModernBERT-base (Warner et al., arXiv:2412.13663)
under PyLate's ColBERT head.

Nothing here imports the program.  It reads the program's seeded weights
from their pytree (layer 0 under ``layer0``, layers 1.. stacked as
(periods, global_every, ...)) and runs the equations one layer at a
time, in a Python loop, with the window mask built densely:

    h = LayerNorm(E[ids])
    layer i:  h = h + Wo_i Attn_i(Norm_attn_i(h))      (Norm_attn_0 = Identity)
              h = h + Wo_mlp_i (GELU(a) * g),  [a, g] = split(Wi_i Norm_mlp_i(h))
    out = normalize(LayerNorm_final(h) @ P)

LayerNorms are bias-free (eps 1e-5), GELU is exact, no linear layer has
a bias; layer i is global (full attention, RoPE base ``rope_theta``) when
``i % global_every == 0``, else local (``|i - j| <= local_window // 2``,
RoPE base ``local_rope_theta``); RoPE is the rotate-half form.  Keys that
a row may not see score -1e30, so a padded row that sees no key averages
them all; only real tokens are compared.

At ``precision="highest"`` everything is fp32 and every product runs at
``Precision.HIGHEST``.  One of ``reference.PRECISIONS`` below it rounds
every matmul operand and every activation (the normed inputs, q, k, v
after RoPE, the attention weights and context, the GeGLU product, the
residual stream) to that precision's mantissa, as ``benchlib.reference``
spells it out; ``reference.lower("bf16")``, fp8, is the control's.
Each layer is one jitted call; documents run in blocks that fit the
device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import reference

EPS = 1e-5
NEG = -1e30


def _ln(x, gamma):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * gamma


def _rope(x, theta: float):
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "first", "n_heads", "theta", "band", "precision"))
def _layer(h, lp, attend, *, first, n_heads, theta, band, precision):
    def act(x):
        return reference.round_weights(x, precision)

    def mm(spec, a, b):
        return reference.einsum(spec, a, b, precision)

    n, s, d = h.shape
    hd = d // n_heads
    a = lp["attn"]
    x = h if first else act(_ln(h, lp["ln1"]))

    def heads(w):
        return mm("nsd,de->nse", x, w).reshape(n, s, n_heads, hd)

    q = act(_rope(heads(a["wq"]), theta))
    k = act(_rope(heads(a["wk"]), theta))
    v = act(heads(a["wv"]))
    scores = mm("nihd,njhd->nhij", q, k) / np.sqrt(hd)
    i = np.arange(s)
    visible = np.ones((s, s), bool)
    if band is not None:
        visible = np.abs(i[:, None] - i[None, :]) <= band
    visible = jnp.asarray(visible)[None, None] & attend[:, None, None, :]
    w = act(jax.nn.softmax(jnp.where(visible, scores, NEG), axis=-1))
    ctx = act(mm("nhij,njhd->nihd", w, v).reshape(n, s, d))
    h = act(h + mm("nsd,de->nse", ctx, a["wo"]))
    up = mm("nsd,de->nse", act(_ln(h, lp["ln2"])), lp["ffn"]["wi"])
    gate, lin = jnp.split(up, 2, axis=-1)
    u = act(jax.nn.gelu(gate, approximate=False) * lin)
    return act(h + mm("nsf,fd->nsd", u, lp["ffn"]["wo"]))


@functools.partial(jax.jit, static_argnames=("precision",))
def _embed(table, gamma, ids, *, precision):
    return reference.round_weights(_ln(table[ids], gamma), precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(h, gamma, proj, *, precision):
    h = reference.round_weights(_ln(h, gamma), precision)
    raw = reference.einsum("nsd,de->nse", h, proj, precision)
    return raw / jnp.maximum(jnp.linalg.norm(raw, axis=-1, keepdims=True),
                             1e-9)


def layer_weights(params: dict, model: dict) -> list:
    """Every layer's weights in fp32, layer 0 first."""
    bb = params["backbone"]
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda a: jnp.asarray(a, jnp.float32))
    out = [f32(bb["layer0"])]
    g = model["global_every"]
    for i in range(1, model["n_layers"]):
        p, k = divmod(i - 1, g)
        out.append(f32(jax.tree_util.tree_map(lambda a: a[p, k],
                                              bb["layers"])))
    return out


def encode_docs(params: dict, model: dict, ids, *,
                precision: str = "highest", block: int = 16):
    """(embeddings (n, S, out_dim) f32, real-token mask (n, S)) of token
    id documents (0 is padding, neither attended to nor kept)."""
    ids = np.asarray(ids)
    mask = ids != 0
    bb = params["backbone"]
    embed = jnp.asarray(bb["embed"], jnp.float32)
    gam = {k: jnp.asarray(bb[k], jnp.float32) for k in ("embed_norm", "ln_f")}
    proj = jnp.asarray(params["proj"], jnp.float32)
    layers = layer_weights(params, model)
    g = model["global_every"]
    out = np.empty(ids.shape + (model["out_dim"],), np.float32)
    for lo in range(0, len(ids), block):
        attend = jnp.asarray(mask[lo:lo + block])
        h = _embed(embed, gam["embed_norm"], jnp.asarray(ids[lo:lo + block]),
                   precision=precision)
        for i, lp in enumerate(layers):
            local = i % g != 0
            h = _layer(h, lp, attend, first=i == 0,
                       n_heads=model["n_heads"],
                       theta=float(model["local_rope_theta"] if local
                                   else model["rope_theta"]),
                       band=model["local_window"] // 2 if local else None,
                       precision=precision)
        out[lo:lo + block] = np.asarray(_head(h, gam["ln_f"], proj,
                                              precision=precision))
    return out, mask


def encode_gap(got, want, mask) -> float:
    """The largest ``1 - cos`` between two embeddings of a token, over the
    real tokens of ``mask``."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    cos = (got * want).sum(-1) / np.maximum(
        np.linalg.norm(got, axis=-1) * np.linalg.norm(want, axis=-1), 1e-30)
    mask = np.asarray(mask, bool)
    return float((1.0 - cos)[mask].max()) if mask.any() else 0.0
