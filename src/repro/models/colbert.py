"""ColBERT-style late-interaction encoder — the paper's own architecture.

A bidirectional transformer backbone (repro.models.transformer in encoder
mode) + a linear projection to the late-interaction dim (128 in
ColBERTv2).  Two output geometries, matching §3 of the paper:

  * ``norm="sphere"`` — L2-normalize onto S^{n-1} (Khattab & Zaharia);
  * ``norm="ball"``   — [27]'s projection *into* the unit ball, required
    by Norm-/LP-pruning and used for the regularized fine-tuning runs.

The backbone is chosen by ``ColBERTConfig.backbone``: the repo's own
pre-norm RMSNorm + RoPE + SwiGLU block (``"rmsnorm_swiglu"``, ColBERTv2
widths in ``configs/colbert_base.py``) or ModernBERT's
(``"modernbert"``, GTE-ModernColBERT-v1 in
``configs/gte_moderncolbert.py``).

Queries are augmented to a fixed length with [MASK] tokens (ColBERT's
query augmentation); documents carry padding masks.  With
``attend_expansion=False`` (PyLate's default) the expansion tokens are
embedded and scored but no token attends to them.  The encoder can also
export per-token received-attention mass for the attention-score pruning
baseline.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.regularizers import ball_projection
from repro.models import attention as attn_lib
from repro.models import transformer as tfm
from repro.models.common import dense_init, rms_norm
from repro.sharding import constrain

MASK_ID = 3  # reserved vocab ids: 0=pad, 1=[Q], 2=[D], 3=[MASK]
BACKBONES = ("rmsnorm_swiglu", "modernbert")


@dataclasses.dataclass(frozen=True)
class ColBERTConfig:
    name: str = "colbert"
    vocab: int = 30_522
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    out_dim: int = 128
    query_len: int = 32
    doc_len: int = 180
    norm: str = "sphere"            # "sphere" | "ball"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    backbone: str = "rmsnorm_swiglu"    # | "modernbert"
    global_every: int = 0           # modernbert: every n-th layer is global
    local_window: int = 0           # modernbert: local layers' window
    rope_theta: float = 1e4         # RoPE base (modernbert: global layers)
    local_rope_theta: float = 1e4   # modernbert: RoPE base of local layers
    attend_expansion: bool = True   # queries attend to [MASK] expansion

    def __post_init__(self):
        if self.backbone not in BACKBONES:
            raise ValueError(f"backbone {self.backbone!r} not in "
                             f"{BACKBONES}")

    def lm_config(self) -> tfm.LMConfig:
        return tfm.LMConfig(
            name=self.name + "-core", n_layers=self.n_layers,
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_heads, d_ff=self.d_ff, vocab=self.vocab,
            causal=False, tie_embeddings=True,
            param_dtype=self.param_dtype, compute_dtype=self.compute_dtype,
            remat=False, block=self.backbone,
            global_every=self.global_every, local_window=self.local_window,
            rope_theta=self.rope_theta,
            local_rope_theta=self.local_rope_theta)

    def param_count(self) -> int:
        return self.lm_config().param_count() + self.d_model * self.out_dim


def init_params(key, cfg: ColBERTConfig):
    kb, kp = jax.random.split(key)
    return {
        "backbone": tfm.init_params(kb, cfg.lm_config()),
        "proj": dense_init(kp, cfg.d_model, cfg.out_dim, cfg.param_dtype),
    }


def _finalize(cfg: ColBERTConfig, raw):
    if cfg.norm == "sphere":
        return raw / jnp.maximum(jnp.linalg.norm(raw, axis=-1, keepdims=True),
                                 1e-9)
    return ball_projection(raw)


def encode(params, cfg: ColBERTConfig, token_ids, attn_mask):
    """token_ids, attn_mask: (B, S) -> unit-sphere/ball embeddings (B,S,out)."""
    h = tfm.hidden_states(params["backbone"], token_ids, cfg.lm_config(),
                          attn_mask=attn_mask)
    raw = h @ params["proj"].astype(cfg.compute_dtype)
    raw = constrain(raw, "batch", "seq", None)
    return _finalize(cfg, raw)


def encode_queries(params, cfg: ColBERTConfig, token_ids):
    """Query augmentation: pad/truncate to query_len with [MASK]; every
    position is scored (masks participate in scoring, per ColBERT), and
    the expansion tokens are attended to unless ``attend_expansion`` is
    off."""
    B, S = token_ids.shape
    if S < cfg.query_len:
        pad = jnp.full((B, cfg.query_len - S), MASK_ID, token_ids.dtype)
        token_ids = jnp.concatenate([token_ids, pad], axis=1)
    else:
        token_ids = token_ids[:, :cfg.query_len]
    real = (token_ids != 0) & (token_ids != MASK_ID)
    token_ids = jnp.where(token_ids == 0, MASK_ID, token_ids)
    mask = jnp.ones_like(token_ids, dtype=bool)
    attend = mask if cfg.attend_expansion else real
    return encode(params, cfg, token_ids, attend), mask


def encode_docs(params, cfg: ColBERTConfig, token_ids):
    mask = token_ids != 0
    return encode(params, cfg, token_ids, mask), mask


def encode_docs_with_attention(params, cfg: ColBERTConfig, token_ids):
    """Doc embeddings + per-token received-attention (first layer) for the
    attention-score pruning baseline."""
    mask = token_ids != 0
    emb = encode(params, cfg, token_ids, mask)
    lm = cfg.lm_config()
    x = params["backbone"]["embed"][token_ids].astype(cfg.compute_dtype)
    layer0 = jax.tree_util.tree_map(lambda a: a[0],
                                    params["backbone"]["layers"])
    ap = attn_lib.AttnParams(**layer0["attn"])
    h = rms_norm(x, layer0["ln1"])
    recv = attn_lib.attention_weights_received(
        ap, h, n_heads=lm.n_heads, n_kv_heads=lm.n_kv_heads,
        head_dim=lm.hd, attn_mask=mask, rope_theta=lm.rope_theta)
    return emb, mask, recv
