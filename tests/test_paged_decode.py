"""Paged decode against a plain per-layer reference.

``decode_step`` carries the stacked, lane-dense K/V pools ``(L, N, bs,
KV·hd)`` through its layer stack and writes each layer's new token into
them in place. The reference below runs the same step one layer at a time
with the cache handling written out in numpy: gather each row's blocks in
table order, a plain softmax over the valid cache entries and the token
itself, and a scatter of the new K/V at ``(l, table[cur_len // bs],
cur_len % bs)``. Projections, norms, rotary phases and the FFN half are
the model's own functions, since they are not what is under test.

The batch is ragged: one row mid-block, one whose write crosses into a
new block, one idle row whose table is all null block, and one with null
tail entries. Every pool entry, unowned blocks included, holds random
values, so a wrong gather or a mask that lets an invalid entry through
moves the logits.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import init_params
from repro.models.layers import (
    apply_rope,
    embed_tokens,
    gated_mlp,
    lm_logits,
    rms_norm,
    rope,
)
from repro.models.model import decode_step, init_paged_decode_cache
from repro.models.moe import identity_placement, moe_layer
from repro.sharding import host_policy

# MoE with grouped-query heads; dense with qkv bias; dense with qk norm
ARCHS = ("granite-moe-3b-a800m", "qwen1.5-4b", "qwen3-32b")
BS, N_BLOCKS = 4, 10
# row 0: 5 tokens in blocks 3, 7 — writes at offset 1 of block 7
# row 1: 8 tokens fill blocks 2, 5 — the write crosses into block 9
# row 2: idle — every entry the null block, cur_len 0
# row 3: 2 tokens in block 4 — null tail entries
TABLES = np.array([[3, 7, 0], [2, 5, 9], [0, 0, 0], [4, 0, 0]], np.int32)
CUR_LEN = np.array([5, 8, 0, 2], np.int32)
TOKENS = np.array([[7], [11], [0], [42]], np.int32)
# float32 throughout; the reference's plain softmax and numpy matmuls
# round differently from the program's online softmax and XLA dots
TOL = dict(rtol=1e-4, atol=1e-4)


def _case(arch, **overrides):
    cfg = dataclasses.replace(get_smoke_config(arch), **overrides)
    policy = host_policy()
    params, _ = init_params(cfg, jax.random.PRNGKey(0), policy, jnp.float32)
    pools = init_paged_decode_cache(cfg, N_BLOCKS, BS, policy, jnp.float32)
    rng = np.random.default_rng(0)
    k_pool, v_pool = (
        rng.standard_normal(pools["attn"][n].shape).astype(np.float32)
        for n in ("k", "v")
    )
    return cfg, policy, params, k_pool, v_pool


def _reference(cfg, policy, params, k_pool, v_pool):
    """Logits and pools after one decode step, layer by layer."""
    L, B, n_max = cfg.num_layers, *TABLES.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G, S_v = H // KV, n_max * BS
    pk, pv = k_pool.copy(), v_pool.copy()
    cos, sin = rope(jnp.asarray(CUR_LEN), hd, cfg.rope_theta)
    placements = identity_placement(cfg, L)
    x = embed_tokens(jnp.asarray(TOKENS), params["embed"], cfg, policy)
    for l in range(L):
        lp = jax.tree.map(lambda t: t[l], params["blocks"])
        a = jax.tree.map(np.asarray, lp["attn"])
        h = np.asarray(rms_norm(x, lp["ln1"], cfg.norm_eps))[:, 0]  # (B, D)
        q, k, v = h @ a["wq"], h @ a["wk"], h @ a["wv"]
        if cfg.qkv_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k, v = q.reshape(B, H, hd), k.reshape(B, KV, hd), v.reshape(B, KV, hd)
        if cfg.qk_norm:
            q = rms_norm(q, a["q_norm"], cfg.norm_eps)
            k = rms_norm(k, a["k_norm"], cfg.norm_eps)
        q = np.asarray(apply_rope(jnp.asarray(q)[:, None], cos[:, None],
                                  sin[:, None]))[:, 0]
        k = np.asarray(apply_rope(jnp.asarray(k)[:, None], cos[:, None],
                                  sin[:, None]))[:, 0]
        # gather each row's logical view, then the token itself
        keys = np.concatenate(
            [pk[l][TABLES].reshape(B, S_v, KV, hd), k[:, None]], axis=1)
        vals = np.concatenate(
            [pv[l][TABLES].reshape(B, S_v, KV, hd), v[:, None]], axis=1)
        valid = np.concatenate(
            [np.arange(S_v)[None] < CUR_LEN[:, None], np.ones((B, 1), bool)],
            axis=1,
        )
        s = np.einsum("bkgd,bskd->bkgs", q.reshape(B, KV, G, hd), keys)
        s = np.where(valid[:, None, None], s / np.sqrt(hd), -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out = np.einsum("bkgs,bskd->bkgd", p, vals).reshape(B, H * hd)
        x = x + jnp.asarray(out @ a["wo"])[:, None]
        # scatter the new K/V into each row's current block
        for b in range(B):
            blk, off = TABLES[b, CUR_LEN[b] // BS], CUR_LEN[b] % BS
            pk[l, blk, off] = k[b].reshape(KV * hd)
            pv[l, blk, off] = v[b].reshape(KV * hd)
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            y, _ = moe_layer(h2, lp["moe"], placements[l], cfg, policy,
                             capacity_factor=cfg.decode_capacity_factor)
        else:
            y = gated_mlp(h2, lp["mlp"], activation=cfg.mlp_activation,
                          policy=policy)
        x = x + y
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(x, params, cfg, policy, mode="decode")[:, 0]
    return np.asarray(logits), pk, pv


@pytest.mark.parametrize("decode_mode", ("scan", "python"))
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_matches_per_layer_reference(arch, decode_mode):
    _check_against_reference(_case(arch), decode_mode)


@pytest.mark.parametrize("decode_mode", ("scan", "python"))
def test_paged_decode_with_stacked_expert_weights(decode_mode):
    """The expert kernel reads each layer's weights out of the stack the
    paged decode hands it; the reference gives it each layer's own. The
    expert width is a multiple of 128, so no padding takes a layer out of
    the stack before the kernel."""
    _check_against_reference(
        _case("granite-moe-3b-a800m", moe_backend="pallas", expert_d_ff=128),
        decode_mode)


def _check_against_reference(case, decode_mode):
    cfg, policy, params, k_pool, v_pool = case
    step = jax.jit(lambda params, caches, cur_len, tables, tokens: decode_step(
        params, caches, cur_len, tokens, cfg, policy, block_tables=tables,
        decode_mode=decode_mode,
    ))
    caches = {"attn": {"k": jnp.asarray(k_pool), "v": jnp.asarray(v_pool)}}
    logits, new_caches, _ = step(params, caches, jnp.asarray(CUR_LEN),
                                 jnp.asarray(TABLES), jnp.asarray(TOKENS))
    ref_logits, ref_k, ref_v = _reference(cfg, policy, params, k_pool, v_pool)
    np.testing.assert_allclose(np.asarray(logits), ref_logits, **TOL)

    written = np.zeros(k_pool.shape[:3], bool)  # (L, N, bs)
    written[:, TABLES[np.arange(len(CUR_LEN)), CUR_LEN // BS],
            CUR_LEN % BS] = True
    assert written.sum() == cfg.num_layers * len(CUR_LEN)  # no two collide
    for name, before, ref in (("k", k_pool, ref_k), ("v", v_pool, ref_v)):
        after = np.asarray(new_caches["attn"][name])
        assert after.shape == before.shape
        # the token lands exactly where the reference put it ...
        np.testing.assert_allclose(after[written], ref[written], **TOL)
        # ... and every other entry is the pool as it was, bit for bit
        np.testing.assert_array_equal(after[~written], before[~written])
