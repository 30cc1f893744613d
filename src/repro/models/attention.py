"""GQA attention: full / causal / sliding-window, train + KV-cache decode.

Pure functions over a params dict.  All activations carry logical-axis
sharding annotations (repro.sharding); GSPMD inserts the collectives.

Cache layout (per layer, stacked by the transformer's scan):
  k, v: (batch, kv_heads, cache_len, head_dim)
where cache_len = max_len for full attention and `window` (ring buffer)
for sliding-window attention — the ring buffer is what makes the
`long_500k` decode shape a bounded-memory problem (DESIGN.md §7).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, rope
from repro.sharding import constrain

NEG = -1e30


class AttnParams(NamedTuple):
    wq: jax.Array            # (d_model, n_heads * head_dim)
    wk: jax.Array            # (d_model, n_kv_heads * head_dim)
    wv: jax.Array            # (d_model, n_kv_heads * head_dim)
    wo: jax.Array            # (n_heads * head_dim, d_model)
    bq: jax.Array | None
    bk: jax.Array | None
    bv: jax.Array | None


def init_attn(key, d_model, n_heads, n_kv_heads, head_dim, qkv_bias,
              dtype) -> AttnParams:
    ks = jax.random.split(key, 4)
    z = lambda n: jnp.zeros((n,), dtype) if qkv_bias else None
    return AttnParams(
        wq=dense_init(ks[0], d_model, n_heads * head_dim, dtype),
        wk=dense_init(ks[1], d_model, n_kv_heads * head_dim, dtype),
        wv=dense_init(ks[2], d_model, n_kv_heads * head_dim, dtype),
        wo=dense_init(ks[3], n_heads * head_dim, d_model, dtype),
        bq=z(n_heads * head_dim), bk=z(n_kv_heads * head_dim),
        bv=z(n_kv_heads * head_dim),
    )


def _project_qkv(p: AttnParams, x, n_heads, n_kv_heads, head_dim):
    B, S, _ = x.shape
    q = x @ p.wq
    k = x @ p.wk
    v = x @ p.wv
    if p.bq is not None:
        q, k, v = q + p.bq, k + p.bk, v + p.bv
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    return q, k, v


def attention(p: AttnParams, x: jax.Array, *, n_heads: int, n_kv_heads: int,
              head_dim: int, causal: bool, window: int | None = None,
              band: int | None = None,
              rope_theta: float | None = 1e4,
              attn_mask: jax.Array | None = None,
              positions: jax.Array | None = None,
              chunk: int | None = None,
              remat_chunk: bool = False) -> jax.Array:
    """Full-sequence attention (training / prefill). x: (B, S, D).

    ``window`` is one-sided (query i sees keys j > i - window); ``band``
    is symmetric (query i sees keys j with |i - j| <= band), the local
    layers of a bidirectional encoder.

    ``chunk`` activates the blocked path: a lax.scan over query chunks so
    the live score buffer is (B, H, chunk, S) instead of (B, H, S, S) —
    the memory-safe path for the 32k-prefill / 4k-train shapes.

    ``remat_chunk`` recomputes each chunk's scores in the backward pass
    instead of letting the scan stack f32 softmax residuals per chunk
    (§Perf: removes a 4x-score-matrix HBM round trip per layer at the
    cost of one extra QK^T matmul in backward).
    """
    B, S, D = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if rope_theta is not None:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)

    group = n_heads // n_kv_heads
    qg = q.reshape(B, S, n_kv_heads, group, head_dim)

    if chunk is None or chunk >= S:
        scores = jnp.einsum("bikgh,bjkh->bkgij", qg, k) / jnp.sqrt(head_dim)
        ii = jnp.arange(S)[:, None]
        jj = jnp.arange(S)[None, :]
        vis = jnp.ones((S, S), bool)
        if causal:
            vis &= jj <= ii
        if window is not None:
            vis &= jj > ii - window
        if band is not None:
            vis &= jnp.abs(jj - ii) <= band
        scores = jnp.where(vis[None, None, None], scores, NEG)
        if attn_mask is not None:  # (B, S) key padding mask
            scores = jnp.where(attn_mask[:, None, None, None, :], scores, NEG)
        w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bkgij,bjkh->bikgh", w, v)
    else:
        n_chunks = -(-S // chunk)
        pad = n_chunks * chunk - S
        qg_p = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        qc = qg_p.reshape(B, n_chunks, chunk, n_kv_heads, group, head_dim)
        qc = jnp.moveaxis(qc, 1, 0)          # (nc, B, chunk, kv, g, hd)
        jj = jnp.arange(S)[None, :]

        def one_chunk(c, q_blk):
            ii = c * chunk + jnp.arange(chunk)[:, None]
            s = jnp.einsum("bikgh,bjkh->bkgij", q_blk, k) / jnp.sqrt(head_dim)
            vis = jnp.ones((chunk, S), bool)
            if causal:
                vis &= jj <= ii
            if window is not None:
                vis &= jj > ii - window
            if band is not None:
                vis &= jnp.abs(jj - ii) <= band
            s = jnp.where(vis[None, None, None], s, NEG)
            if attn_mask is not None:
                s = jnp.where(attn_mask[:, None, None, None, :], s, NEG)
            w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
            return jnp.einsum("bkgij,bjkh->bikgh", w, v)

        if remat_chunk:
            one_chunk = jax.checkpoint(one_chunk, prevent_cse=False)
        ctx = jax.lax.scan(
            lambda _, cq: (None, one_chunk(cq[0], cq[1])),
            None, (jnp.arange(n_chunks), qc))[1]      # (nc, B, chunk, kv, g, hd)
        ctx = jnp.moveaxis(ctx, 0, 1).reshape(B, n_chunks * chunk,
                                              n_kv_heads, group, head_dim)
        ctx = ctx[:, :S]
    ctx = ctx.reshape(B, S, n_heads * head_dim)
    ctx = constrain(ctx, "batch", "seq", "heads")
    return ctx @ p.wo


def attention_weights_received(p: AttnParams, x, *, n_heads, n_kv_heads,
                               head_dim, attn_mask=None, rope_theta=None):
    """Mean attention mass received per token (column sums) — feeds the
    attention-score pruning baseline [17, 20].  Bidirectional only."""
    B, S, D = x.shape
    q, k, _ = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    if rope_theta is not None:
        pos = jnp.arange(S)[None, :]
        q, k = rope(q, pos, rope_theta), rope(k, pos, rope_theta)
    group = n_heads // n_kv_heads
    qg = q.reshape(B, S, n_kv_heads, group, head_dim)
    scores = jnp.einsum("bikgh,bjkh->bkgij", qg, k) / jnp.sqrt(head_dim)
    if attn_mask is not None:
        scores = jnp.where(attn_mask[:, None, None, None, :], scores, NEG)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    recv = w.mean(axis=(1, 2, 3))          # (B, S) column mass per key token
    return recv


class KVCache(NamedTuple):
    k: jax.Array       # (B, kv_heads, C, head_dim)
    v: jax.Array       # (B, kv_heads, C, head_dim)


def init_cache(batch, n_kv_heads, cache_len, head_dim, dtype) -> KVCache:
    shape = (batch, n_kv_heads, cache_len, head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def decode_attention(p: AttnParams, x: jax.Array, cache: KVCache,
                     pos: jax.Array, *, n_heads: int, n_kv_heads: int,
                     head_dim: int, window: int | None = None,
                     rope_theta: float | None = 1e4
                     ) -> tuple[jax.Array, KVCache]:
    """One-token decode. x: (B, 1, D); pos: scalar current position.

    Full attention: cache holds positions [0, C); slot = pos.
    Sliding window: cache is a ring buffer of size `window`; slot =
    pos % window and only the last `window` positions are visible.
    """
    B, S1, D = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim)
    pos_b = jnp.full((B, 1), pos, jnp.int32)
    if rope_theta is not None:
        q = rope(q, pos_b, rope_theta)
        k = rope(k, pos_b, rope_theta)
    C = cache.k.shape[2]
    slot = (pos % C).astype(jnp.int32)
    knew = jnp.swapaxes(k, 1, 2)           # (B, kv, 1, hd)
    vnew = jnp.swapaxes(v, 1, 2)
    ck = jax.lax.dynamic_update_slice(cache.k, knew.astype(cache.k.dtype),
                                      (0, 0, slot, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, vnew.astype(cache.v.dtype),
                                      (0, 0, slot, 0))
    ck = constrain(ck, "batch", "kv_heads", "kv_len", None)
    cv = constrain(cv, "batch", "kv_heads", "kv_len", None)

    group = n_heads // n_kv_heads
    qg = q.reshape(B, n_kv_heads, group, head_dim)
    scores = jnp.einsum("bkgh,bkjh->bkgj", qg, ck) / jnp.sqrt(head_dim)
    j = jnp.arange(C)
    if window is None:
        valid = j <= pos
    else:
        # Ring buffer: slot j holds absolute position pos - ((slot-j) mod C);
        # valid iff that position has been written (>= 0).  age < C already
        # bounds visibility to the window.
        age = (slot - j) % C
        valid = (pos - age) >= 0
    scores = jnp.where(valid[None, None, None, :], scores, NEG)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bkgj,bkjh->bkgh", w, cv)
    ctx = ctx.reshape(B, 1, n_heads * head_dim)
    return ctx @ p.wo, KVCache(ck, cv)
