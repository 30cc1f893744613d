"""gte-moderncolbert — GTE-ModernColBERT-v1 (lightonai, PyLate).

ModernBERT-base backbone (Warner et al., arXiv:2412.13663;
answerdotai/ModernBERT-base config.json): vocab 50,368, 22 layers,
hidden 768, 12 heads of 64, GeGLU ``intermediate_size`` 1152, no bias
in any linear layer or norm, LayerNorm eps 1e-5.  Layer i is global
(full attention, RoPE base 160,000) when ``i % 3 == 0``, else local
(``|i - j| <= 64`` of ``local_attention`` 128, RoPE base 10,000).  The
PyLate head projects 768 -> 128 with no bias and L2-normalises;
``query_length`` 32 with [MASK] expansion not attended to
(``attend_to_expansion_tokens`` false), ``document_length`` 300.

Assumed, not checked against the model card here: the expansion-token
mask above, and the repo's reserved ids (0 pad, 1 [Q], 2 [D], 3 [MASK])
in place of ModernBERT's tokenizer ids; weights are seeded, not loaded.
"""

import jax.numpy as jnp

from repro.configs import base
from repro.models.colbert import ColBERTConfig

CONFIG = ColBERTConfig(name="gte-moderncolbert", vocab=50_368, n_layers=22,
                       d_model=768, n_heads=12, d_ff=1152, out_dim=128,
                       query_len=32, doc_len=300, norm="sphere",
                       param_dtype=jnp.bfloat16, compute_dtype=jnp.bfloat16,
                       backbone="modernbert", global_every=3,
                       local_window=128, rope_theta=160_000.0,
                       local_rope_theta=10_000.0, attend_expansion=False)

# Global, local, local, global: one whole period after layer 0.
SMOKE = ColBERTConfig(name="gte-moderncolbert-smoke", vocab=512, n_layers=4,
                      d_model=64, n_heads=4, d_ff=96, out_dim=32,
                      query_len=8, doc_len=40, norm="sphere",
                      backbone="modernbert", global_every=3, local_window=8,
                      rope_theta=160_000.0, local_rope_theta=10_000.0,
                      attend_expansion=False)

SHAPES = {
    "prune_index": base.ShapeSpec(
        "prune_index", "serve",
        {"docs_per_block": 64, "doc_len": 300, "n_samples": 10_000,
         "out_dim": 128}),
}

base.register(base.ArchEntry(
    arch_id="gte-moderncolbert", family="retrieval", config=CONFIG,
    smoke=SMOKE, shapes=SHAPES,
    notes="a published late-interaction encoder beside the repo's own "
          "block; indexed at PyLate's 300-token documents"))
