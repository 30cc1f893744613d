# Pallas TPU kernels for the paper's compute hot spots:
#   maxsim_topk    — fused top-K-of-matmul (Voronoi pruning shortlist rescan)
#   colbert_maxsim — batched late-interaction scoring (rerank/serve)
#   embedding_bag  — fused recsys table lookup + reduce
#   flash_attention— online-softmax attention forward (memory-bound LM fix)
# Each package: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper w/ interpret fallback off-TPU), ref.py (pure-jnp oracle).
