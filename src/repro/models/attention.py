"""Attention: sequence-parallel train/prefill and model-sharded-KV decode.

Two entry points (see DESIGN.md §4):

* :func:`attention_train` — queries stay *sequence-sharded* over the model
  axis (each device attends its query slice against an all-gathered K/V), so
  any head count partitions exactly (musicgen 24H, qwen1.5 20H, qwen2.5 40H
  included — no padding). Queries are processed in chunks so the score
  matrix never materializes at (S × S). Sliding-window attention slices a
  static-width KV window per chunk (true O(S·w) compute); full causal
  attention masks a full-width rectangle per chunk (the ~2× flop overhead vs
  ideal causal is measured and attacked in EXPERIMENTS.md §Perf).

* :func:`attention_decode` — one new token against a KV cache whose sequence
  dim is sharded over the model axis. Softmax statistics over the sharded
  dim reduce via small all-reduces (flash-decoding); the new token's K/V is
  folded in analytically, so no concatenation along a sharded dim ever
  happens. The cache update is a one-hot blend (touches the whole cache —
  bandwidth measured in §Roofline; see §Perf for the dynamic-slice variant).

Weights are stored model-sharded on flat head dims; the train path
explicitly all-gathers them per layer (ZeRO-3), the decode path consumes
them sharded (tensor-parallel) because decode activations are tiny.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..sharding.policy import ShardingPolicy
from .layers import apply_rope, rms_norm, rope

__all__ = [
    "init_attention",
    "attention_train",
    "attention_decode",
    "attention_decode_paged",
    "AttnCache",
]

NEG_INF = -1e30


def init_attention(
    key, config: ModelConfig, *, num_layers: int, dtype, policy: ShardingPolicy
):
    D = config.d_model
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    ks = jax.random.split(key, 4)
    s = float(1.0 / np.sqrt(D))
    so = float(1.0 / np.sqrt(H * hd))
    params = {
        "wq": jax.random.normal(ks[0], (num_layers, D, H * hd), dtype) * s,
        "wk": jax.random.normal(ks[1], (num_layers, D, KV * hd), dtype) * s,
        "wv": jax.random.normal(ks[2], (num_layers, D, KV * hd), dtype) * s,
        "wo": jax.random.normal(ks[3], (num_layers, H * hd, D), dtype) * so,
    }
    specs = {
        "wq": policy.w_col(),
        "wk": policy.w_col(),
        "wv": policy.w_col(),
        "wo": policy.w_row(),
    }
    if config.qkv_bias:
        params["bq"] = jnp.zeros((num_layers, H * hd), dtype)
        params["bk"] = jnp.zeros((num_layers, KV * hd), dtype)
        params["bv"] = jnp.zeros((num_layers, KV * hd), dtype)
        specs["bq"] = policy.spec(None, policy.model_axis)
        specs["bk"] = policy.spec(None, policy.model_axis)
        specs["bv"] = policy.spec(None, policy.model_axis)
    if config.qk_norm:
        params["q_norm"] = jnp.zeros((num_layers, config.head_dim), dtype)
        params["k_norm"] = jnp.zeros((num_layers, config.head_dim), dtype)
        specs["q_norm"] = policy.w_vector()
        specs["k_norm"] = policy.w_vector()
    return params, specs


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AttnCache:
    """KV cache for one attention site: (B, S_max, KV, hd), seq over model."""

    k: jax.Array
    v: jax.Array

    def tree_flatten(self):
        return (self.k, self.v), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def zeros(batch, max_len, config: ModelConfig, dtype, extra_leading=()):
        shape = (*extra_leading, batch, max_len, config.num_kv_heads, config.head_dim)
        return AttnCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _project_qkv(x, p, config: ModelConfig, *, gather_weights: bool,
                 policy: ShardingPolicy):
    """x (B, S, D) → q (B,S,H,hd), k/v (B,S,KV,hd) (pre-RoPE)."""
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    wq, wk, wv = p["wq"], p["wk"], p["wv"]
    if gather_weights:
        # ZeRO-3: materialize full projection weights for this layer only.
        wq = policy.constrain(wq, None, None)
        wk = policy.constrain(wk, None, None)
        wv = policy.constrain(wv, None, None)
    q = jnp.einsum("bsd,de->bse", x, wq)
    k = jnp.einsum("bsd,de->bse", x, wk)
    v = jnp.einsum("bsd,de->bse", x, wv)
    if config.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    B, S = x.shape[:2]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if config.qk_norm:
        q = rms_norm(q, p["q_norm"], config.norm_eps)
        k = rms_norm(k, p["k_norm"], config.norm_eps)
    return q, k, v


def _grouped(q, config: ModelConfig):
    """(B, S, H, hd) → (B, S, KV, G, hd) with G = H // KV (GQA groups)."""
    B, S = q.shape[:2]
    KV = config.num_kv_heads
    G = config.num_heads // KV
    return q.reshape(B, S, KV, G, config.head_dim)


def attention_train(
    x,
    p,
    config: ModelConfig,
    policy: ShardingPolicy,
    *,
    start_pos: int = 0,
    q_chunk: int = 512,
    return_cache: bool = False,
):
    """Causal (optionally sliding-window) self-attention, sequence-parallel.

    x (B, S, D) — residual stream, sequence-sharded over model. Returns
    (out (B, S, D) sequence-sharded, cache | None).
    """
    B, S, D = x.shape
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    G = H // KV
    q, k, v = _project_qkv(x, p, config, gather_weights=True, policy=policy)
    positions = start_pos + jnp.arange(S)
    cos, sin = rope(positions, hd, config.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # queries stay sequence-sharded; K/V replicate across the model axis
    q = policy.constrain(q, policy.batch, policy.model_axis, None, None)
    k = policy.constrain(k, policy.batch, None, None, None)
    v = policy.constrain(v, policy.batch, None, None, None)

    scale = 1.0 / np.sqrt(hd)
    window = config.sliding_window if config.sliding_window > 0 else 0

    # Shard-aligned chunking: S = M (sequence shards, over `model`) × n_sub
    # (sequential sub-chunks) × cq (rows per step). Every lax.map step keeps
    # all M shards busy on their own cq query rows.
    M = policy.model_axis_size
    if S % M:
        M = 1  # smoke-scale fallback: no sequence sharding
    per_shard = S // M
    cq = min(q_chunk, per_shard)
    while per_shard % cq:
        cq -= 1
    n_sub = per_shard // cq

    qg = _grouped(q, config).reshape(B, M, n_sub, cq, KV, G, hd)
    qg = policy.constrain(
        qg, policy.batch, policy.model_axis, None, None, None, None, None
    )
    shard_base = jnp.arange(M) * per_shard  # (M,) global offset per shard
    kv_len = min(window + cq, S) if window else S

    def chunk_attn(j):
        q_blk = jax.lax.dynamic_slice_in_dim(qg, j, 1, axis=2)[:, :, 0]
        q_pos = start_pos + shard_base[:, None] + j * cq + jnp.arange(cq)  # (M, cq)
        if window:
            # per-shard static-width KV window, gathered from replicated K/V
            kv_start = jnp.clip(q_pos[:, -1] + 1 - kv_len, 0, S - kv_len)
            idx = kv_start[:, None] + jnp.arange(kv_len)  # (M, kv_len)
            k_blk = jnp.take(k, idx, axis=1)  # (B, M, kv_len, KV, hd)
            v_blk = jnp.take(v, idx, axis=1)
            k_pos = start_pos + idx  # (M, kv_len)
            logits = jnp.einsum(
                "bmqkgd,bmskd->bmkgqs", q_blk, k_blk,
                preferred_element_type=jnp.float32,
            ) * scale
        else:
            k_pos = start_pos + jnp.broadcast_to(jnp.arange(S), (M, S))
            logits = jnp.einsum(
                "bmqkgd,bskd->bmkgqs", q_blk, k,
                preferred_element_type=jnp.float32,
            ) * scale
        mask = q_pos[:, :, None] >= k_pos[:, None, :]  # (M, cq, kv)
        if window:
            mask &= (q_pos[:, :, None] - k_pos[:, None, :]) < window
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        if window:
            out = jnp.einsum(
                "bmkgqs,bmskd->bmqkgd", probs.astype(v.dtype), v_blk
            )
        else:
            out = jnp.einsum("bmkgqs,bskd->bmqkgd", probs.astype(v.dtype), v)
        return out  # (B, M, cq, KV, G, hd)

    if n_sub == 1:
        out = chunk_attn(0)
    else:
        out = jax.lax.map(chunk_attn, jnp.arange(n_sub))
        out = out.transpose(1, 2, 0, 3, 4, 5, 6)  # (B, M, n_sub, cq, KV, G, hd)
    out = out.reshape(B, S, H * hd)
    out = policy.constrain(out, policy.batch, policy.model_axis, None)

    wo = policy.constrain(p["wo"], None, None)  # ZeRO-3 gather
    y = jnp.einsum("bse,ed->bsd", out, wo)
    y = policy.constrain(y, policy.batch, policy.model_axis, None)

    cache = None
    if return_cache:
        k_c = policy.constrain(k, policy.batch, policy.model_axis, None, None)
        v_c = policy.constrain(v, policy.batch, policy.model_axis, None, None)
        cache = AttnCache(k_c, v_c)
    return y, cache


def attention_decode(
    x,
    p,
    cache: AttnCache,
    cur_len,
    config: ModelConfig,
    policy: ShardingPolicy,
):
    """One decode step. x (B, 1, D) replicated over model; cache seq-sharded.

    Returns (out (B, 1, D), updated cache). ``cur_len`` (scalar int32) is the
    number of valid positions already in the cache; the new token is written
    at index ``cur_len`` (mod window for SWA).
    """
    B = x.shape[0]
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    G = H // KV
    S_max = cache.k.shape[-3]

    # TP projections: flat head dim sharded; gather the (tiny) activations.
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k_new = jnp.einsum("bsd,de->bse", x, p["wk"])
    v_new = jnp.einsum("bsd,de->bse", x, p["wv"])
    if config.qkv_bias:
        q = q + p["bq"]
        k_new = k_new + p["bk"]
        v_new = v_new + p["bv"]
    q = policy.constrain(q, policy.batch, None, None)
    k_new = policy.constrain(k_new, policy.batch, None, None)
    v_new = policy.constrain(v_new, policy.batch, None, None)
    q = q.reshape(B, 1, H, hd)
    k_new = k_new.reshape(B, 1, KV, hd)
    v_new = v_new.reshape(B, 1, KV, hd)
    if config.qk_norm:
        q = rms_norm(q, p["q_norm"], config.norm_eps)
        k_new = rms_norm(k_new, p["k_norm"], config.norm_eps)
    cos, sin = rope(cur_len[None], hd, config.rope_theta)
    q = apply_rope(q, cos[None], sin[None])
    k_new = apply_rope(k_new, cos[None], sin[None])

    window = config.sliding_window if config.sliding_window > 0 else 0
    write_pos = jnp.mod(cur_len, S_max) if window else cur_len

    qg = _grouped(q, config)[:, 0]  # (B, KV, G, hd)
    scale = 1.0 / np.sqrt(hd)
    # Scores over the (sharded) cache. The cache stays in its storage dtype —
    # mixed-precision einsums accumulate in fp32 via preferred_element_type,
    # so XLA never materializes an fp32 copy of the whole cache (which it
    # would otherwise hoist out of the layer scan: +2× cache bytes of temp).
    s_cache = jnp.einsum(
        "bkgd,bskd->bkgs", qg.astype(cache.k.dtype), cache.k,
        preferred_element_type=jnp.float32,
    ) * scale  # (B, KV, G, S_max) fp32
    pos = jnp.arange(S_max)
    if window:
        # valid cache entries: the last `min(cur_len, window)` writes
        age = jnp.mod(write_pos - pos, S_max)  # steps since slot was written
        valid = (age >= 1) & (age <= jnp.minimum(cur_len, window - 1))
    else:
        valid = pos < cur_len
    s_cache = jnp.where(valid[None, None, None], s_cache, NEG_INF)
    s_new = jnp.einsum(
        "bkgd,bkd->bkg", qg.astype(jnp.float32),
        k_new[:, 0].astype(jnp.float32),
    )[..., None] * scale  # (B, KV, G, 1) — the token attends to itself

    # two-piece online softmax (no concat along the sharded dim)
    m = jnp.maximum(jnp.max(s_cache, axis=-1, keepdims=True), s_new)
    e_cache = jnp.exp(s_cache - m)
    e_new = jnp.exp(s_new - m)
    denom = jnp.sum(e_cache, axis=-1, keepdims=True) + e_new
    out_cache = jnp.einsum(
        "bkgs,bskd->bkgd", e_cache.astype(cache.v.dtype), cache.v,
        preferred_element_type=jnp.float32,
    )
    out = (out_cache + e_new * v_new[:, 0, :, None].astype(jnp.float32)) / denom
    out = out.reshape(B, 1, H * hd).astype(x.dtype)

    # row-parallel output projection: shard the flat dim, psum the result
    out = policy.constrain(out, policy.batch, None, None)
    y = jnp.einsum("bse,ed->bsd", out, p["wo"])
    y = policy.constrain(y, policy.batch, None, None)

    if config.decode_cache_update == "dus":
        # in-place single-slot write: O(token) bytes instead of O(cache)
        zero = jnp.zeros((), jnp.int32)
        start = (zero, write_pos.astype(jnp.int32), zero, zero)
        new_k = jax.lax.dynamic_update_slice(
            cache.k, k_new.astype(cache.k.dtype), start
        )
        new_v = jax.lax.dynamic_update_slice(
            cache.v, v_new.astype(cache.v.dtype), start
        )
    else:
        # one-hot blend: rewrites the whole cache but partitions trivially
        oh = (pos == write_pos).astype(cache.k.dtype)[None, :, None, None]
        new_k = cache.k * (1 - oh) + k_new.astype(cache.k.dtype) * oh
        new_v = cache.v * (1 - oh) + v_new.astype(cache.v.dtype) * oh
    new_k = policy.kv_cache(new_k[None])[0]
    new_v = policy.kv_cache(new_v[None])[0]
    return y, AttnCache(new_k, new_v)


def attention_decode_paged(
    x,
    p,
    k_pool,
    v_pool,
    layer,
    block_tables,
    cur_len,
    config: ModelConfig,
    policy: ShardingPolicy,
):
    """One decode step of layer ``layer`` against the stacked paged KV pools.

    x (B, 1, D); ``k_pool``/``v_pool`` (L, N, bs, KV·hd) — every layer's
    shared block pool, with heads and head dim flattened into one
    lane-dense minor dim (see :func:`~repro.models.model.init_paged_decode_cache`);
    ``layer`` int32 scalar — which layer's pool to read and write, so the
    stacked pools are indexed in place and no layer is sliced out;
    ``block_tables`` (B, n_max) int32 maps each row's logical positions
    ``[0, n_max·bs)`` onto physical blocks (block 0 is the null block:
    inactive rows and unallocated tail entries point there); ``cur_len``
    (B,) int32 — per-row valid lengths, so ragged batches need no
    shared-max zero-panel approximation. The new token is written at
    physical ``(layer, table[cur_len // bs], cur_len % bs)``; rows whose
    table entry is the null block scatter harmlessly into block 0, which
    active rows never own and masked scores never read.

    Returns (out (B, 1, D), (new_k_pool, new_v_pool)): the stacked pools
    with this layer's token written. Sliding-window attention is not
    supported on the paged path — the engine keeps the dense cache for
    those archs.
    """
    B = x.shape[0]
    H, KV, hd = config.num_heads, config.num_kv_heads, config.head_dim
    bs = k_pool.shape[2]
    n_max = block_tables.shape[-1]
    S_v = n_max * bs  # logical view length

    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k_new = jnp.einsum("bsd,de->bse", x, p["wk"])
    v_new = jnp.einsum("bsd,de->bse", x, p["wv"])
    if config.qkv_bias:
        q = q + p["bq"]
        k_new = k_new + p["bk"]
        v_new = v_new + p["bv"]
    q = policy.constrain(q, policy.batch, None, None)
    k_new = policy.constrain(k_new, policy.batch, None, None)
    v_new = policy.constrain(v_new, policy.batch, None, None)
    q = q.reshape(B, 1, H, hd)
    k_new = k_new.reshape(B, 1, KV, hd)
    v_new = v_new.reshape(B, 1, KV, hd)
    if config.qk_norm:
        q = rms_norm(q, p["q_norm"], config.norm_eps)
        k_new = rms_norm(k_new, p["k_norm"], config.norm_eps)
    # per-row rotary phase: each row is at its own position
    cos, sin = rope(cur_len, hd, config.rope_theta)  # (B, hd/2)
    q = apply_rope(q, cos[:, None], sin[:, None])
    k_new = apply_rope(k_new, cos[:, None], sin[:, None])

    # gather each row's logical cache view through its block table
    k_view = k_pool[layer, block_tables].reshape(B, S_v, KV, hd)
    v_view = v_pool[layer, block_tables].reshape(B, S_v, KV, hd)

    qg = _grouped(q, config)[:, 0]  # (B, KV, G, hd)
    scale = 1.0 / np.sqrt(hd)
    s_cache = jnp.einsum(
        "bkgd,bskd->bkgs", qg.astype(k_view.dtype), k_view,
        preferred_element_type=jnp.float32,
    ) * scale  # (B, KV, G, S_v) fp32
    pos = jnp.arange(S_v)
    valid = pos[None, :] < cur_len[:, None]  # (B, S_v) — ragged masking
    s_cache = jnp.where(valid[:, None, None, :], s_cache, NEG_INF)
    s_new = jnp.einsum(
        "bkgd,bkd->bkg", qg.astype(jnp.float32),
        k_new[:, 0].astype(jnp.float32),
    )[..., None] * scale  # (B, KV, G, 1)

    # two-piece online softmax, identical to the dense decode path
    m = jnp.maximum(jnp.max(s_cache, axis=-1, keepdims=True), s_new)
    e_cache = jnp.exp(s_cache - m)
    e_new = jnp.exp(s_new - m)
    denom = jnp.sum(e_cache, axis=-1, keepdims=True) + e_new
    out_cache = jnp.einsum(
        "bkgs,bskd->bkgd", e_cache.astype(v_view.dtype), v_view,
        preferred_element_type=jnp.float32,
    )
    out = (out_cache + e_new * v_new[:, 0, :, None].astype(jnp.float32)) / denom
    out = out.reshape(B, 1, H * hd).astype(x.dtype)

    out = policy.constrain(out, policy.batch, None, None)
    y = jnp.einsum("bse,ed->bsd", out, p["wo"])
    y = policy.constrain(y, policy.batch, None, None)

    # scatter the new K/V into each row's current block (blocks are
    # uniquely owned, so active rows never collide; null-block rows may —
    # last-writer-wins into storage that is never validly read)
    blk = jnp.take_along_axis(
        block_tables, (cur_len // bs)[:, None], axis=1
    )[:, 0]  # (B,) physical block per row
    off = cur_len % bs
    new_k_pool = k_pool.at[layer, blk, off].set(
        k_new[:, 0].reshape(B, KV * hd).astype(k_pool.dtype)
    )
    new_v_pool = v_pool.at[layer, blk, off].set(
        v_new[:, 0].reshape(B, KV * hd).astype(v_pool.dtype)
    )
    return y, (new_k_pool, new_v_pool)
