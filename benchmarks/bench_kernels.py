"""Kernel micro-benchmarks (interpret-mode correctness cost on CPU; TPU
perf is assessed via the dry-run roofline — see EXPERIMENTS.md §Roofline).

Reported per kernel: us/call of the fused kernel vs its materialize-
everything jnp reference at a Voronoi-estimator-shaped workload, plus
the HBM bytes the fusion avoids (the actual TPU win).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.kernels.colbert_maxsim.ops import (colbert_maxsim_multi_op,
                                              colbert_maxsim_op)
from repro.kernels.colbert_maxsim.ref import (colbert_maxsim_multi_ref,
                                              colbert_maxsim_ref)
from repro.kernels.embedding_bag.ops import embedding_bag_op
from repro.kernels.embedding_bag.ref import embedding_bag_ref


def main():
    key = jax.random.PRNGKey(0)
    dim = 128

    # colbert_maxsim at rerank shape
    nd, md, l = 64, 32, 8
    q = jax.random.normal(key, (l, dim))
    docs = jax.random.normal(jax.random.fold_in(key, 2), (nd, md, dim))
    msk = jnp.ones((nd, md), bool)
    t_k, _ = common.timeit(lambda: colbert_maxsim_op(q, docs, msk), repeat=3)
    t_r, _ = common.timeit(
        lambda: jax.jit(colbert_maxsim_ref)(q, docs, msk), repeat=3)
    common.csv_line("kernels/colbert_maxsim_fused", t_k * 1e6,
                    f"ref_us={t_r*1e6:.1f};"
                    f"hbm_bytes_avoided={nd*md*l*4}")

    # colbert_maxsim_multi at a query-batch serving shape
    nq = 8
    qb = jax.random.normal(jax.random.fold_in(key, 6), (nq, l, dim))
    t_k, _ = common.timeit(
        lambda: colbert_maxsim_multi_op(qb, docs, msk, block_d=16), repeat=3)
    t_r, _ = common.timeit(
        lambda: jax.jit(colbert_maxsim_multi_ref)(qb, docs, msk), repeat=3)
    common.csv_line("kernels/colbert_maxsim_multi_fused", t_k * 1e6,
                    f"ref_us={t_r*1e6:.1f};"
                    f"hbm_bytes_avoided={nq*nd*md*l*4}")

    # embedding_bag at recsys lookup shape
    V, Dd, nb, nnz = 5000, 64, 256, 4
    table = jax.random.normal(key, (V, Dd))
    ids = jax.random.randint(jax.random.fold_in(key, 3), (nb, nnz), 0, V)
    t_k, _ = common.timeit(lambda: embedding_bag_op(table, ids), repeat=3)
    t_r, _ = common.timeit(
        lambda: jax.jit(embedding_bag_ref)(table, ids), repeat=3)
    common.csv_line("kernels/embedding_bag_fused", t_k * 1e6,
                    f"ref_us={t_r*1e6:.1f};"
                    f"hbm_bytes_avoided={nb*nnz*Dd*4}")

    # flash attention at a prefill-ish tile
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import flash_attention_ref
    Hf, Sf, dd = 4, 256, 64
    qf = jax.random.normal(key, (Hf, Sf, dd))
    kf = jax.random.normal(jax.random.fold_in(key, 4), (Hf, Sf, dd))
    vf = jax.random.normal(jax.random.fold_in(key, 5), (Hf, Sf, dd))
    t_k, _ = common.timeit(lambda: flash_attention_op(qf, kf, vf,
                                                      causal=True), repeat=2)
    t_r, _ = common.timeit(lambda: jax.jit(
        lambda a, b, c: flash_attention_ref(a, b, c, causal=True))(qf, kf, vf),
        repeat=2)
    common.csv_line("kernels/flash_attention_fwd", t_k * 1e6,
                    f"ref_us={t_r*1e6:.1f};"
                    f"hbm_bytes_avoided={Hf*Sf*Sf*4}")


if __name__ == "__main__":
    main()
