"""Training driver: config-driven, checkpoint/restart-safe, elastic-aware.

  PYTHONPATH=src python -m repro.launch.train --arch colbert \
      --preset smoke --steps 50 --ckpt-dir /tmp/ckpt --ckpt-every 20

Restart semantics: the driver always restores the newest valid checkpoint
and resumes the step-indexed data pipeline at the restored step — rerun
the same command after a kill and training continues bit-exactly (tested
in tests/test_train_driver.py).  On real fleets the elastic hooks
(repro.train.elastic) re-plan the mesh from survivors; on this host the
mesh is whatever the host offers.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import pipeline, synthetic
from repro.launch import compile_cache
from repro.models import colbert as colbert_lib
from repro.models import gnn as gnn_lib
from repro.models import recsys as recsys_lib
from repro.models import transformer as tfm
from repro.train import checkpoint, elastic, optimizer, train_step


def build_trainable(arch: str, preset: str, batch: int, seq: int,
                    opt_cfg: optimizer.AdamWConfig):
    """Returns (init_fn, step_fn, make_batch)."""
    entry = configs.get(arch)
    cfg = entry.smoke if preset == "smoke" else entry.config

    if entry.family == "lm":
        return (
            lambda k: tfm.init_params(k, cfg),
            train_step.lm_train_step(cfg, opt_cfg),
            lambda s: synthetic.lm_batch(0, s, batch, seq, cfg.vocab),
        )
    if entry.family == "retrieval":
        corpus = synthetic.token_corpus(0, n_docs=max(batch * 4, 64),
                                        n_q=max(batch * 4, 64),
                                        vocab=cfg.vocab,
                                        m=cfg.doc_len, l=cfg.query_len)

        def make_batch(s):
            rng = np.random.default_rng(s)
            qi = rng.integers(0, corpus.q_ids.shape[0], batch)
            # positive doc: first relevant doc per query
            rel = np.asarray(corpus.rel)
            di = np.array([np.flatnonzero(rel[q])[0] if rel[q].any() else 0
                           for q in qi])
            return {"query_ids": corpus.q_ids[qi], "doc_ids":
                    corpus.doc_ids[di]}

        return (
            lambda k: colbert_lib.init_params(k, cfg),
            train_step.colbert_train_step(cfg, opt_cfg, reg="sim",
                                          alpha=0.1),
            make_batch,
        )
    if entry.family == "gnn":
        from repro.data import graph_sampler
        g = graph_sampler.synthetic_graph(0, n_nodes=200, n_edges=1000,
                                          d_feat=cfg.d_feat,
                                          n_classes=cfg.n_classes)
        batch_d = {"x": jnp.asarray(g.x),
                   "edge_index": jnp.asarray(g.edge_index),
                   "labels": jnp.asarray(g.labels),
                   "edge_mask": jnp.ones((g.n_edges,), bool),
                   "label_mask": jnp.ones((g.n_nodes,), jnp.float32)}
        return (
            lambda k: gnn_lib.init_params(k, cfg),
            train_step.gin_train_step(cfg, opt_cfg),
            lambda s: batch_d,
        )
    # recsys
    if arch == "bert4rec":
        def make_batch(s):
            key = jax.random.fold_in(jax.random.PRNGKey(0), s)
            ks = jax.random.split(key, 4)
            B, S, M, N = batch, cfg.seq_len, 4, 32
            return {
                "items": jax.random.randint(ks[0], (B, S), 4, cfg.n_items),
                "mask_idx": jax.random.randint(ks[1], (B, M), 0, S),
                "labels": jax.random.randint(ks[2], (B, M), 4, cfg.n_items),
                "negatives": jax.random.randint(ks[3], (N,), 4, cfg.n_items),
            }

        def loss_fn(params, b):
            pos, neg = recsys_lib.bert4rec_sampled_logits(
                params, cfg, b["items"], b["mask_idx"], b["labels"],
                b["negatives"])
            return recsys_lib.sampled_softmax_loss(pos, neg)

        def step(state, b):
            loss, grads = jax.value_and_grad(loss_fn)(state["params"], b)
            params, opt, stats = optimizer.apply(opt_cfg, state["params"],
                                                 grads, state["opt"])
            return ({"params": params, "opt": opt,
                     "step": state["step"] + 1}, {"loss": loss, **stats})

        return (lambda k: recsys_lib.bert4rec_init(k, cfg), step, make_batch)

    init = {"dlrm-rm2": recsys_lib.dlrm_init, "dcn-v2": recsys_lib.dcn_init,
            "wide-deep": recsys_lib.widedeep_init}[arch]
    fwd = {
        "dlrm-rm2": lambda p, b: recsys_lib.dlrm_forward(
            p, cfg, b["dense"], b["sparse_ids"]),
        "dcn-v2": lambda p, b: recsys_lib.dcn_forward(
            p, cfg, b["dense"], b["sparse_ids"]),
        "wide-deep": lambda p, b: recsys_lib.widedeep_forward(
            p, cfg, b["sparse_ids"]),
    }[arch]
    return (
        lambda k: init(k, cfg),
        train_step.ctr_train_step(fwd, opt_cfg),
        lambda s: synthetic.ctr_batch(0, s, batch, 13, cfg.n_sparse,
                                      cfg.table_rows),
    )


def run(arch: str, *, preset: str = "smoke", steps: int = 50, batch: int = 8,
        seq: int = 32, ckpt_dir: str | None = None, ckpt_every: int = 25,
        log_every: int = 10, lr: float = 1e-3, seed: int = 0,
        stop_after: int | None = None) -> dict:
    """`steps` fixes the optimizer schedule (the job's target length);
    `stop_after` simulates preemption mid-job — training halts there and
    a rerun of the same command resumes bit-exactly."""
    opt_cfg = optimizer.AdamWConfig(lr=lr, warmup_steps=min(20, steps // 5),
                                    total_steps=steps)
    init_fn, step_fn, make_batch = build_trainable(arch, preset, batch, seq,
                                                   opt_cfg)
    state = train_step.make_train_state(jax.random.PRNGKey(seed), init_fn,
                                        opt_cfg)
    start = 0
    if ckpt_dir:
        restored_step, restored = checkpoint.restore_latest(ckpt_dir, state)
        if restored is not None:
            state, start = restored, restored_step
            print(f"[train] resumed from step {start}")

    jit_step = jax.jit(step_fn, donate_argnums=(0,))
    monitor = elastic.StragglerMonitor()
    pipe = pipeline.StepIndexedPipeline(make_batch, start_step=start,
                                        prefetch=2)
    metrics = {}
    losses = []
    t_train0 = time.time()
    stop = steps if stop_after is None else min(stop_after, steps)
    try:
        for s, batch_d in pipe:
            if s >= stop:
                break
            t0 = time.time()
            state, metrics = jit_step(state, batch_d)
            loss = float(metrics["loss"])
            losses.append(loss)
            monitor.record("host0", time.time() - t0)
            if log_every and s % log_every == 0:
                print(f"[train] step {s} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f}")
            if ckpt_dir and ckpt_every and (s + 1) % ckpt_every == 0:
                checkpoint.save_async(ckpt_dir, s + 1, state)
    finally:
        pipe.close()
    if ckpt_dir:
        checkpoint.save(ckpt_dir, stop, state)
        checkpoint.wait_pending()
    wall = time.time() - t_train0
    return {"state": state, "final_loss": losses[-1] if losses else None,
            "losses": losses, "wall_s": wall, "start": start}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.all_archs())
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()
    compile_cache.enable()
    out = run(args.arch, preset=args.preset, steps=args.steps,
              batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
              ckpt_every=args.ckpt_every, lr=args.lr)
    print(f"[train] done: final loss {out['final_loss']:.4f} "
          f"({out['wall_s']:.1f}s)")


if __name__ == "__main__":
    main()
