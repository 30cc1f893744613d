"""Unified transformer LM: dense & MoE blocks, GQA, optional SWA, RoPE.

Design points for the multi-pod posture:
  * layers are **stacked** (leading L axis) and executed with
    ``jax.lax.scan`` — HLO stays O(1) in depth, which keeps the 512-device
    dry-run compiles tractable and lets XLA overlap the per-layer FSDP
    all-gather of layer l+1 with the compute of layer l;
  * every projection carries logical-axis annotations so one model body
    serves all sharding postures (FSDP+TP baseline, fully-sharded batch,
    sequence-parallel hillclimb variant);
  * ``remat`` wraps the block for training (checkpoint policy: save only
    the carry) — activations per device stay O(B_local * S * D).

Modes: causal LM (train/prefill/decode) and bidirectional encoder
(ColBERT / BERT4Rec backbones).

Two encoder blocks: the default pre-norm RMSNorm + RoPE + SwiGLU block,
and ModernBERT's (``block="modernbert"``, Warner et al., arXiv:2412.13663):
bias-free LayerNorm (eps 1e-5), a normed embedding and no attention norm
in layer 0, a GeGLU FFN, and layer i global (full attention, RoPE base
``rope_theta``) when ``i % global_every == 0``, else local (``|i - j| <=
local_window // 2``, RoPE base ``local_rope_theta``).  Its layer 0 runs
unrolled and the rest as a scan over stacked periods of ``global_every``
layers (locals, then the global), so the HLO stays O(1) in depth and every
mask is static.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models.common import (dense_init, embed_init, geglu, layer_norm,
                                 rms_norm, swiglu)
from repro.sharding import constrain


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                  # 0 -> d_model // n_heads
    moe_experts: int = 0               # 0 -> dense FFN
    moe_top_k: int = 0
    capacity_factor: float = 1.25
    qkv_bias: bool = False
    window: int | None = None          # sliding-window attention
    attn_window_serving: int | None = None  # window used only for long-ctx serving
    rope_theta: float = 1e4
    causal: bool = True                # False -> bidirectional encoder
    tie_embeddings: bool = False
    attn_chunk: int | None = None      # blocked attention chunk (long seqs)
    remat_attn_chunk: bool = False     # recompute chunk scores in backward
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    block: str = "rmsnorm_swiglu"      # | "modernbert" (encoder only)
    global_every: int = 0              # modernbert: global iff i % this == 0
    local_window: int = 0              # modernbert local: |i - j| <= this // 2
    local_rope_theta: float = 1e4      # modernbert: RoPE base of local layers

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def param_count(self) -> int:
        """Analytic parameter count (for MODEL_FLOPS = 6*N*D)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        attn = d * self.n_heads * self.hd * 2 + d * self.n_kv_heads * self.hd * 2
        if self.moe_experts:
            ffn = self.moe_experts * 3 * d * f + d * self.moe_experts
        else:
            ffn = 3 * d * f
        # ModernBERT's embedding norm stands in for layer 0's attention
        # norm, so the count is the same for both blocks.
        per_layer = attn + ffn + 2 * d
        emb = v * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of E experts)."""
        if not self.moe_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        dense_ffn = self.moe_experts * 3 * d * f
        active_ffn = self.moe_top_k * 3 * d * f
        return self.param_count() - self.n_layers * (dense_ffn - active_ffn)


def init_layer(key, cfg: LMConfig):
    ka, kf, kn = jax.random.split(key, 3)
    p = {
        "ln1": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "ln2": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "attn": init_attn_params(ka, cfg),
    }
    if cfg.moe_experts:
        p["moe"] = moe_lib.init_moe(kf, cfg.d_model, cfg.d_ff,
                                    cfg.moe_experts,
                                    cfg.param_dtype)._asdict()
    elif cfg.block == "modernbert":
        k1, k2 = jax.random.split(kf)
        p["ffn"] = {
            "wi": dense_init(k1, cfg.d_model, 2 * cfg.d_ff, cfg.param_dtype),
            "wo": dense_init(k2, cfg.d_ff, cfg.d_model, cfg.param_dtype),
        }
    else:
        k1, k2, k3 = jax.random.split(kf, 3)
        p["ffn"] = {
            "w_gate": dense_init(k1, cfg.d_model, cfg.d_ff, cfg.param_dtype),
            "w_up": dense_init(k2, cfg.d_model, cfg.d_ff, cfg.param_dtype),
            "w_down": dense_init(k3, cfg.d_ff, cfg.d_model, cfg.param_dtype),
        }
    del kn
    return p


def init_attn_params(key, cfg: LMConfig):
    return attn_lib.init_attn(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                              cfg.hd, cfg.qkv_bias, cfg.param_dtype)._asdict()


def init_params(key, cfg: LMConfig):
    if cfg.block == "modernbert":
        return _init_modernbert(key, cfg)
    ke, kl, kh = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    params = {
        "embed": embed_init(ke, cfg.vocab, cfg.d_model, cfg.param_dtype),
        "layers": layers,
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kh, cfg.d_model, cfg.vocab,
                                       cfg.param_dtype, scale=0.02)
    return params


def _block(cfg: LMConfig, x, layer, attn_mask, window):
    ap = attn_lib.AttnParams(**layer["attn"])
    h = rms_norm(x, layer["ln1"])
    h = attn_lib.attention(
        ap, h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd, causal=cfg.causal, window=window,
        rope_theta=cfg.rope_theta, attn_mask=attn_mask,
        chunk=cfg.attn_chunk, remat_chunk=cfg.remat_attn_chunk)
    x = x + h
    x = constrain(x, "batch", "seq", "embed")
    h = rms_norm(x, layer["ln2"])
    if cfg.moe_experts:
        h, aux = moe_lib.moe_ffn(moe_lib.MoEParams(**layer["moe"]), h,
                                 top_k=cfg.moe_top_k,
                                 capacity_factor=cfg.capacity_factor)
    else:
        f = layer["ffn"]
        h = swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
        aux = {"load_balance": jnp.zeros(()), "router_z": jnp.zeros(())}
    x = x + h
    x = constrain(x, "batch", "seq", "embed")
    return x, aux


def forward(params, tokens, cfg: LMConfig, *, attn_mask=None,
            window: int | None = "cfg"):
    """Full-sequence forward -> (logits, aux).  tokens: (B, S) int32."""
    if window == "cfg":
        window = cfg.window
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    x = constrain(x, "batch", "seq", "embed")

    def body(carry, layer):
        y, aux = _block(cfg, carry, layer, attn_mask, window)
        return y, aux

    blk = body
    if cfg.remat:
        blk = jax.checkpoint(body, prevent_cse=False)
    x, auxs = jax.lax.scan(blk, x, params["layers"])
    x = rms_norm(x, params["ln_f"])
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = x @ head.astype(cfg.compute_dtype)
    logits = constrain(logits, "batch", "seq", "vocab")
    aux = {k: v.mean() for k, v in auxs.items()}
    return logits, aux


def hidden_states(params, tokens, cfg: LMConfig, *, attn_mask=None):
    """Final-layer hidden states (encoder mode for retrieval backbones)."""
    if cfg.block == "modernbert":
        return _modernbert_hidden_states(params, tokens, cfg, attn_mask)
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    x = constrain(x, "batch", "seq", "embed")

    def body(carry, layer):
        return _block(cfg, carry, layer, attn_mask, cfg.window)

    blk = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    x, _ = jax.lax.scan(blk, x, params["layers"])
    return rms_norm(x, params["ln_f"])


# ------------------------ ModernBERT encoder -------------------------------

MODERNBERT_NORM_EPS = 1e-5


def _modernbert_periods(cfg: LMConfig) -> int:
    """Stacked periods after the unrolled layer 0."""
    g = cfg.global_every
    if cfg.causal or g < 1 or (cfg.n_layers - 1) % g:
        raise ValueError(
            f"{cfg.name}: the modernbert block is a bidirectional encoder "
            f"of 1 + k * global_every layers; got causal={cfg.causal}, "
            f"n_layers={cfg.n_layers}, global_every={g}")
    return (cfg.n_layers - 1) // g


def _init_modernbert(key, cfg: LMConfig):
    """Layer 0 on its own, without an attention norm (``ln1``); layers
    1.. stacked as (periods, global_every, ...)."""
    n_periods = _modernbert_periods(cfg)
    ke, kl = jax.random.split(key)
    keys = jax.random.split(kl, cfg.n_layers)
    layer0 = init_layer(keys[0], cfg)
    del layer0["ln1"]
    rest = jax.vmap(lambda k: init_layer(k, cfg))(keys[1:])
    rest = jax.tree_util.tree_map(
        lambda a: a.reshape((n_periods, cfg.global_every) + a.shape[1:]),
        rest)
    return {
        "embed": embed_init(ke, cfg.vocab, cfg.d_model, cfg.param_dtype),
        "embed_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "layer0": layer0,
        "layers": rest,
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
    }


def _modernbert_block(cfg: LMConfig, x, layer, attn_mask, *, local: bool):
    """One ModernBERT layer; a layer without ``ln1`` (layer 0) feeds the
    attention its input as it is."""
    ap = attn_lib.AttnParams(**layer["attn"])
    h = x
    if "ln1" in layer:
        h = layer_norm(x, layer["ln1"], eps=MODERNBERT_NORM_EPS)
    h = attn_lib.attention(
        ap, h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd, causal=False,
        band=cfg.local_window // 2 if local else None,
        rope_theta=cfg.local_rope_theta if local else cfg.rope_theta,
        attn_mask=attn_mask)
    x = constrain(x + h, "batch", "seq", "embed")
    h = layer_norm(x, layer["ln2"], eps=MODERNBERT_NORM_EPS)
    h = geglu(h, layer["ffn"]["wi"], layer["ffn"]["wo"])
    return constrain(x + h, "batch", "seq", "embed")


def _modernbert_hidden_states(params, tokens, cfg: LMConfig, attn_mask):
    g = cfg.global_every
    _modernbert_periods(cfg)
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    x = layer_norm(x, params["embed_norm"], eps=MODERNBERT_NORM_EPS)
    x = constrain(x, "batch", "seq", "embed")
    x = _modernbert_block(cfg, x, params["layer0"], attn_mask, local=False)

    def period(carry, layers):
        for k in range(g):
            layer = jax.tree_util.tree_map(lambda a: a[k], layers)
            carry = _modernbert_block(cfg, carry, layer, attn_mask,
                                      local=k < g - 1)
        return carry, None

    blk = jax.checkpoint(period, prevent_cse=False) if cfg.remat else period
    x, _ = jax.lax.scan(blk, x, params["layers"])
    return layer_norm(x, params["ln_f"], eps=MODERNBERT_NORM_EPS)


# --------------------------- decode path ----------------------------------

def init_cache(cfg: LMConfig, batch: int, max_len: int, *,
               window: int | None = None):
    """Stacked per-layer KV cache.  SWA -> ring buffer of size window."""
    w = window if window is not None else cfg.window
    C = min(max_len, w) if w else max_len
    one = attn_lib.init_cache(batch, cfg.n_kv_heads, C, cfg.hd,
                              cfg.compute_dtype)
    stack = lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape)
    return {"k": stack(one.k), "v": stack(one.v)}


def decode_step(params, cache, tokens, pos, cfg: LMConfig, *,
                window: int | None = "cfg"):
    """One decode step. tokens: (B, 1); pos: scalar. -> (logits, cache)."""
    if window == "cfg":
        window = cfg.window
    x = params["embed"][tokens].astype(cfg.compute_dtype)

    def body(carry, layer_and_cache):
        layer, ck, cv = layer_and_cache
        ap = attn_lib.AttnParams(**layer["attn"])
        h = rms_norm(carry, layer["ln1"])
        h, new_cache = attn_lib.decode_attention(
            ap, h, attn_lib.KVCache(ck, cv), pos, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, window=window,
            rope_theta=cfg.rope_theta)
        x2 = carry + h
        h = rms_norm(x2, layer["ln2"])
        if cfg.moe_experts:
            h, _ = moe_lib.moe_ffn(moe_lib.MoEParams(**layer["moe"]), h,
                                   top_k=cfg.moe_top_k,
                                   capacity_factor=cfg.capacity_factor)
        else:
            f = layer["ffn"]
            h = swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
        return x2 + h, (new_cache.k, new_cache.v)

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["ln_f"])
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = x @ head.astype(cfg.compute_dtype)
    return logits, {"k": nk, "v": nv}
