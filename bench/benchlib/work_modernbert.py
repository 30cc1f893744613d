"""The least encoder work of ModernBERT-base (GTE-ModernColBERT-v1),
computed from shapes.

As ``work.encoder_flops`` for the repo's own block, the count is of the
algorithm on the document's real tokens alone: padding slots, masked
score entries and the softmax are left out, so a share computed from it
reads low if anything.  Norms and the embedding lookup are not counted.
"""

from __future__ import annotations


def local_pairs(n: int, window: int) -> int:
    """(i, j) pairs of ``n`` tokens with ``|i - j| <= window // 2``."""
    h = min(window // 2, n - 1)
    return n + 2 * sum(n - k for k in range(1, h + 1)) if n else 0


def n_global(model: dict) -> int:
    """Layers i with ``i % global_every == 0``."""
    return -(-model["n_layers"] // model["global_every"])


def encoder_flops(n_real: int, model: dict) -> int:
    """Forward FLOPs of encoding one document of ``n_real`` real tokens:
    per layer the Q, K, V and output projections (8 n d^2), the score and
    value products over the pairs a layer attends (4 d each pair: n^2 in
    a global layer, ``local_pairs`` in a local one) and GeGLU (a d x 2f
    and an f x d matrix: 6 n d f); then the projection to ``out_dim``."""
    n, d, f = n_real, model["d_model"], model["d_ff"]
    g = n_global(model)
    dense = model["n_layers"] * (8 * n * d * d + 6 * n * d * f)
    attn = 4 * d * (g * n * n + (model["n_layers"] - g)
                    * local_pairs(n, model["local_window"]))
    return dense + attn + 2 * n * d * model["out_dim"]
