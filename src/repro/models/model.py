"""Model assembly: embed → scan(blocks) → norm → logits, for all 10 archs.

Entry points (all pure functions):

  * :func:`init_params`    — (params, specs) with per-layer weights stacked on
    a leading L dim so the layer stack lowers to one ``lax.scan`` body.
  * :func:`forward_train`  — full-sequence forward returning sequence-sharded
    logits and MoE aux (expert counts per layer for GEM's Step-1).
  * :func:`prefill`        — forward + KV/SSM caches, last-position logits.
  * :func:`decode_step`    — one token against the caches.

Architecture families:
  dense/audio/vlm : [ln → attn → ln → mlp] × L
  moe             : [ln → attn → ln → moe] × L (placement tables threaded)
  ssm             : [ln → mamba2] × L
  hybrid (zamba2) : stages of ``attn_every`` mamba blocks followed by one
                    *shared-weight* attention+MLP block (single param copy,
                    per-stage KV caches)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..sharding.policy import ShardingPolicy
from .attention import (
    AttnCache,
    attention_decode,
    attention_decode_paged,
    attention_train,
    init_attention,
)
from .layers import (
    cross_entropy_loss,
    embed_tokens,
    gated_mlp,
    init_gated_mlp,
    lm_logits,
    rms_norm,
)
from .moe import MoEAux, identity_placement, init_moe, moe_layer
from .ssm import SSMCache, init_ssm, ssm_decode, ssm_train

__all__ = [
    "init_params",
    "forward_train",
    "loss_fn",
    "prefill",
    "decode_step",
    "init_decode_cache",
    "init_paged_decode_cache",
]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _hybrid_split(config: ModelConfig) -> tuple[int, int]:
    """(#layers inside staged scan, #leftover trailing mamba layers)."""
    n_stages = config.num_layers // config.attn_every
    staged = n_stages * config.attn_every
    return staged, config.num_layers - staged


def init_params(config: ModelConfig, key, policy: ShardingPolicy,
                dtype=jnp.bfloat16):
    L = config.num_layers
    D = config.d_model
    keys = jax.random.split(key, 8)
    params: dict[str, Any] = {}
    specs: dict[str, Any] = {}

    V = config.padded_vocab  # padded rows never receive gradient signal:
    # the embedding lookup can't select them and the logit mask zeroes them.
    params["embed"] = jax.random.normal(keys[0], (V, D), dtype) * 0.02
    specs["embed"] = (
        policy.embed_tied() if config.tie_embeddings else policy.embed_untied()
    )
    if not config.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[1], (D, V), dtype) * 0.02
        specs["lm_head"] = policy.lm_head()
    params["final_norm"] = jnp.zeros((D,), dtype)
    specs["final_norm"] = policy.spec(None)

    blocks: dict[str, Any] = {}
    bspecs: dict[str, Any] = {}
    if config.ssm_state > 0:
        blocks["ln"] = jnp.zeros((L, D), dtype)
        bspecs["ln"] = policy.w_vector()
        blocks["ssm"], bspecs["ssm"] = init_ssm(
            keys[2], config, num_layers=L, dtype=dtype, policy=policy
        )
    else:
        blocks["ln1"] = jnp.zeros((L, D), dtype)
        blocks["ln2"] = jnp.zeros((L, D), dtype)
        bspecs["ln1"] = policy.w_vector()
        bspecs["ln2"] = policy.w_vector()
        blocks["attn"], bspecs["attn"] = init_attention(
            keys[3], config, num_layers=L, dtype=dtype, policy=policy
        )
        if config.is_moe:
            blocks["moe"], bspecs["moe"] = init_moe(
                keys[4], config, num_layers=L, dtype=dtype, policy=policy
            )
        else:
            blocks["mlp"], bspecs["mlp"] = init_gated_mlp(
                keys[4], D, config.d_ff, num_layers=L, dtype=dtype, policy=policy
            )
    params["blocks"] = blocks
    specs["blocks"] = bspecs

    if config.is_hybrid:
        shared: dict[str, Any] = {}
        sspecs: dict[str, Any] = {}
        shared["ln1"] = jnp.zeros((1, D), dtype)
        shared["ln2"] = jnp.zeros((1, D), dtype)
        sspecs["ln1"] = policy.w_vector()
        sspecs["ln2"] = policy.w_vector()
        shared["attn"], sspecs["attn"] = init_attention(
            keys[5], config, num_layers=1, dtype=dtype, policy=policy
        )
        shared["mlp"], sspecs["mlp"] = init_gated_mlp(
            keys[6], D, config.d_ff, num_layers=1, dtype=dtype, policy=policy
        )
        params["shared"] = shared
        specs["shared"] = sspecs
    return params, specs


def _slice_layer(tree, idx):
    return jax.tree.map(lambda t: t[idx], tree)


def _scan_or_unroll(f, init, xs, mode: str):
    """Run the layer-stack body ``f`` over stacked ``xs``.

    ``"scan"`` lowers the stack to one ``lax.scan`` — a single traced
    body whose per-layer weights, placement tables, and caches are
    *scanned operands*, so one jitted executable serves any placement /
    replica layout / mid-run migration without retracing. ``"python"``
    unrolls the same body as a host loop (one program per layer) — the
    debugging/baseline mode the parity gates compare against
    token-for-token. Outputs are stacked to match scan's (L, …) layout.
    """
    if mode == "scan":
        return jax.lax.scan(f, init, xs)
    if mode != "python":
        raise ValueError(f"unknown layer-stack mode {mode!r}")
    n = jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        carry, y = f(carry, _slice_layer(xs, i))
        ys.append(y)
    stacked = jax.tree.map(lambda *ts: jnp.stack(ts), *ys)
    return carry, stacked


# ---------------------------------------------------------------------------
# Blocks (train / prefill path: residual sequence-sharded)
# ---------------------------------------------------------------------------

def _split_stacked_experts(blocks):
    """``blocks`` without the stacked expert weights, and those weights.
    A layer scan closes over the weights and hands them to the expert
    kernel whole with the scanned layer index, so no layer's are sliced
    out of the stack."""
    moe_p = dict(blocks["moe"])
    experts = {n: moe_p.pop(n) for n in ("w_gate", "w_up", "w_down")}
    return {**blocks, "moe": moe_p}, experts


def _attn_block_train(x, lp, placement_l, config: ModelConfig,
                      policy: ShardingPolicy, *, return_cache: bool,
                      capacity_factor=None, layer=None, experts=None):
    # experts: the stacked expert weights, read at ``layer`` in place;
    # None when ``lp`` holds this layer's own
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["ln1"], config.norm_eps)
        a, cache = attention_train(
            h, lp["attn"], config, policy, return_cache=return_cache
        )
        if cache is not None:
            cache = {"k": cache.k, "v": cache.v}
        x = x + a
    with jax.named_scope("ffn"):
        h2 = rms_norm(x, lp["ln2"], config.norm_eps)
        aux = None
        if config.is_moe:
            h2 = policy.act_bsd(h2)  # gather tokens across the model axis
            y, aux = moe_layer(
                h2, {**lp["moe"], **(experts or {})}, placement_l, config,
                policy, capacity_factor=capacity_factor,
                seq_sharded_out=True, layer=layer,
            )
        else:
            h2 = policy.act_bsd(h2)
            y = gated_mlp(
                h2, lp["mlp"], activation=config.mlp_activation,
                policy=policy, seq_sharded_out=True,
            )
        x = policy.act_seq_sharded(x + y)
    return x, cache, aux


def _ssm_block_train(x, lp, config: ModelConfig, policy: ShardingPolicy,
                     *, return_cache: bool):
    h = rms_norm(x, lp["ln"], config.norm_eps)
    h = policy.act_bsd(h)  # SSM scans the full sequence: gather over model
    y, cache = ssm_train(h, lp["ssm"], config, policy, return_cache=return_cache)
    if cache is not None:
        cache = _ssm_named(cache.tree())
    x = policy.act_seq_sharded(x + policy.act_seq_sharded(y))
    return x, cache


def _moe_aux_zero(config: ModelConfig, num_slots: int | None = None):
    S = (
        num_slots if num_slots is not None
        else config.num_experts * config.expert_tp
    )
    return MoEAux(
        expert_counts=jnp.zeros((config.num_experts,), jnp.int32),
        aux_loss=jnp.asarray(0.0, jnp.float32),
        dropped=jnp.asarray(0.0, jnp.float32),
        dropped_tokens=jnp.asarray(0, jnp.int32),
        overflow_tokens=jnp.asarray(0, jnp.int32),
        shed_tokens=jnp.asarray(0, jnp.int32),
        shed_delta=jnp.zeros((S,), jnp.int32),
    )


def _stack_forward(x, params, placements, config: ModelConfig,
                   policy: ShardingPolicy, *, return_cache: bool,
                   remat: bool, capacity_factor=None,
                   stack_mode: str = "scan"):
    """Run the whole layer stack. Returns (x, caches, moe_aux)."""
    blocks = params["blocks"]

    if config.is_hybrid:
        staged, leftover = _hybrid_split(config)
        n_stages = staged // config.attn_every
        shared = params["shared"]

        def stage_body(xc, stage_blocks):
            def inner(xc2, lp):
                xc2, cache = _ssm_block_train(
                    xc2, lp, config, policy, return_cache=return_cache
                )
                return xc2, cache
            if remat:
                inner = jax.checkpoint(inner)
            xc, ssm_caches = _scan_or_unroll(inner, xc, stage_blocks, stack_mode)
            # shared attention + MLP block (one weight copy)
            sp = _slice_layer(shared, 0)

            def shared_block(xc2):
                h = rms_norm(xc2, sp["ln1"], config.norm_eps)
                a, cache = attention_train(
                    h, sp["attn"], config, policy, return_cache=return_cache
                )
                xc2 = xc2 + a
                h2 = rms_norm(xc2, sp["ln2"], config.norm_eps)
                h2 = policy.act_bsd(h2)
                y = gated_mlp(
                    h2, sp["mlp"], activation=config.mlp_activation,
                    policy=policy, seq_sharded_out=True,
                )
                if cache is not None:
                    cache = {"k": cache.k, "v": cache.v}
                return policy.act_seq_sharded(xc2 + y), cache
            if remat:
                shared_block = jax.checkpoint(shared_block)
            xc, attn_cache = shared_block(xc)
            return xc, (ssm_caches, attn_cache)

        staged_blocks = jax.tree.map(
            lambda t: t[:staged].reshape(n_stages, config.attn_every, *t.shape[1:]),
            blocks,
        )
        x, (ssm_caches, attn_caches) = _scan_or_unroll(
            stage_body, x, staged_blocks, stack_mode
        )
        tail_caches = None
        if leftover:
            tail_blocks = jax.tree.map(lambda t: t[staged:], blocks)

            def tail(xc, lp):
                xc, cache = _ssm_block_train(
                    xc, lp, config, policy, return_cache=return_cache
                )
                return xc, cache
            if remat:
                tail = jax.checkpoint(tail)
            x, tail_caches = _scan_or_unroll(tail, x, tail_blocks, stack_mode)
        caches = {
            "ssm_staged": ssm_caches, "attn": attn_caches, "ssm_tail": tail_caches,
        } if return_cache else None
        return x, caches, None

    if config.is_ssm:
        def body(xc, lp):
            xc, cache = _ssm_block_train(
                xc, lp, config, policy, return_cache=return_cache
            )
            return xc, cache
        if remat:
            body = jax.checkpoint(body)
        x, caches = _scan_or_unroll(body, x, blocks, stack_mode)
        return x, ({"ssm": caches} if return_cache else None), None

    # attention families
    if placements is None:
        placements = identity_placement(config, config.num_layers)
    experts, layers = None, None
    if config.is_moe and return_cache:
        # prefill, forward only, reads the expert stacks in place.
        # Training keeps them scanned: the in-place read has no VJP
        blocks, experts = _split_stacked_experts(blocks)
        layers = jnp.arange(config.num_layers, dtype=jnp.int32)

    def body(xc, inputs):
        (lp, placement_l), layer = inputs
        xc, cache, aux = _attn_block_train(
            xc, lp, placement_l, config, policy,
            return_cache=return_cache, capacity_factor=capacity_factor,
            layer=layer, experts=experts,
        )
        if aux is None:
            aux = _moe_aux_zero(config) if config.is_moe else 0.0
        return xc, (cache, aux)
    if remat:
        body = jax.checkpoint(body)
    with jax.named_scope("layer_scan"):
        x, (caches, auxes) = _scan_or_unroll(
            body, x, ((blocks, placements), layers), stack_mode
        )
    moe_aux = auxes if config.is_moe else None
    return x, ({"attn": caches} if return_cache else None), moe_aux


def _embed_input(params, batch, config: ModelConfig, policy: ShardingPolicy):
    """tokens (+ optional patch embeddings) → (B, S, D) sequence-sharded."""
    x = embed_tokens(batch["tokens"], params["embed"], config, policy)
    if config.frontend == "vision" and "patches" in batch:
        # precomputed patch embeddings from the stubbed vision frontend
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    return policy.act_seq_sharded(x)


def forward_train(params, batch, config: ModelConfig, policy: ShardingPolicy,
                  placements=None, *, remat: bool = True,
                  stack_mode: str = "scan"):
    """batch: tokens (B, S[-P]), optional patches (B, P, D), labels (B, S).

    Returns (logits (B, S, V) sequence-sharded, aux dict).
    """
    x = _embed_input(params, batch, config, policy)
    x, _, moe_aux = _stack_forward(
        x, params, placements, config, policy, return_cache=False,
        remat=remat, stack_mode=stack_mode,
    )
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = lm_logits(x, params, config, policy, mode="train")
    aux = {}
    if moe_aux is not None:
        # moe_aux is the scan-stacked MoEAux struct: fields are (L, ...)
        aux["expert_counts"] = moe_aux.expert_counts  # (L, E)
        aux["aux_loss"] = jnp.mean(moe_aux.aux_loss)
        aux["dropped"] = jnp.mean(moe_aux.dropped)
        aux["dropped_tokens"] = jnp.sum(moe_aux.dropped_tokens)
    return logits, aux


def loss_fn(params, batch, config: ModelConfig, policy: ShardingPolicy,
            placements=None, *, remat: bool = True,
            stack_mode: str = "scan"):
    logits, aux = forward_train(
        params, batch, config, policy, placements, remat=remat,
        stack_mode=stack_mode,
    )
    mask = batch.get("loss_mask")
    loss = cross_entropy_loss(logits, batch["labels"], mask=mask)
    if config.is_moe:
        loss = loss + config.router_aux_coef * aux["aux_loss"]
    return loss, aux


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------

def prefill(params, batch, config: ModelConfig, policy: ShardingPolicy,
            placements=None, *, stack_mode: str = "scan"):
    """Returns (last-position logits (B, V), caches)."""
    x = _embed_input(params, batch, config, policy)
    x, caches, _ = _stack_forward(
        x, params, placements, config, policy, return_cache=True,
        remat=False, stack_mode=stack_mode,
    )
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], config.norm_eps)
        last = policy.constrain(x[:, -1:], policy.batch, None, None)
        logits = lm_logits(last, params, config, policy, mode="decode")
    return logits[:, 0], caches


def init_decode_cache(config: ModelConfig, batch: int, max_len: int,
                      policy: ShardingPolicy, dtype=jnp.bfloat16):
    """Zero caches shaped for ``decode_step`` (used by input_specs too)."""
    L = config.num_layers
    caches: dict[str, Any] = {}
    window = config.sliding_window
    attn_len = min(window, max_len) if window else max_len

    def kv(leading):
        c = AttnCache.zeros(batch, attn_len, config, dtype, extra_leading=leading)
        return {"k": policy.kv_cache(c.k), "v": policy.kv_cache(c.v)}

    if config.is_hybrid:
        staged, leftover = _hybrid_split(config)
        n_stages = staged // config.attn_every
        caches["ssm_staged"] = _ssm_tree(
            config, batch, (n_stages, config.attn_every), dtype, policy
        )
        caches["attn"] = kv((n_stages,))
        if leftover:
            caches["ssm_tail"] = _ssm_tree(config, batch, (leftover,), dtype, policy)
    elif config.is_ssm:
        caches["ssm"] = _ssm_tree(config, batch, (L,), dtype, policy)
    else:
        caches["attn"] = kv((L,))
    return caches


def init_paged_decode_cache(config: ModelConfig, num_blocks: int,
                            block_size: int, policy: ShardingPolicy,
                            dtype=jnp.bfloat16):
    """Paged KV pools for ``decode_step(..., block_tables=...)``.

    Shape ``(L, N, block_size, KV·hd)`` per K/V: a shared block pool per
    layer instead of per-slot ``max_len`` panels — logical sequences map
    onto blocks through the per-request tables managed by
    :class:`repro.serving.kv_cache.PagedKVPool`. Heads and head dim share
    one minor dim so that it is lane-dense: with ``KV·hd`` a multiple of
    128 the TPU's default layout is row-major and unpadded, whereas a
    minor ``hd`` of 64 fills half a 128-lane tile and the compiler makes
    the block dim minor instead, relayout-copying a layer's pool around
    every per-token write. ``decode_step`` carries both stacked pools
    through its layer scan and indexes them in place. Attention-family
    archs without a sliding window only (SSM/hybrid state is O(1) per
    slot and needs no paging; SWA's ring-buffer ages don't survive the
    block indirection).
    """
    if config.is_ssm or config.is_hybrid:
        raise ValueError("paged KV cache requires an attention-family arch")
    if config.sliding_window > 0:
        raise ValueError("paged KV cache does not support sliding windows")
    L = config.num_layers
    shape = (L, num_blocks, block_size, config.num_kv_heads * config.head_dim)
    # pools are deliberately unconstrained (replicated on a mesh): the
    # block dim is neither a batch nor a sequence axis, so the dense
    # layout's kv_cache spec does not apply
    return {"attn": {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}}


def _ssm_tree(config, batch, leading, dtype, policy: ShardingPolicy):
    c = SSMCache.zeros(batch, config, dtype, extra_leading=leading)
    m = policy.model_axis
    lead = (None,) * len(leading)
    cb = policy.cache_batch
    return {
        "state": policy.constrain(c.state, *lead, cb, m, None, None),
        "conv_x": policy.constrain(c.conv_x, *lead, cb, None, m),
        "conv_b": policy.constrain(c.conv_b, *lead, cb, None, None),
        "conv_c": policy.constrain(c.conv_c, *lead, cb, None, None),
    }


def decode_step(params, caches, cur_len, tokens, config: ModelConfig,
                policy: ShardingPolicy, placements=None, *,
                block_tables=None, decode_mode: str = "scan",
                shed_enables=None):
    """One serving step: tokens (B, 1) int32.

    Dense mode (``block_tables=None``): ``cur_len`` is a scalar int32
    shared by the batch and caches are per-slot ``max_len`` panels.
    Paged mode: ``block_tables`` (B, n_max) int32 and ``cur_len`` (B,)
    int32 route each row's cache traffic through its own block table
    (see :func:`init_paged_decode_cache`) — ragged batches attend at
    their true lengths; the stacked pools ride in the layer scan's carry
    and each layer writes its token into them in place. Returns (logits
    (B, V), new caches, moe aux or None).

    ``decode_mode`` picks the layer-stack lowering contract
    (:func:`_scan_or_unroll`): ``"scan"`` compiles the whole MoE decode
    step as **one** ``lax.scan`` executable whose per-layer router
    tables, replica tables, slot layouts (``placements``) and dense
    caches are scanned operands — any placement or mid-run migration reuses
    the same compiled program; ``"python"`` unrolls the identical body
    per layer, the baseline the scan≡python token-parity gates diff
    against.

    ``shed_enables`` (L,) 0/1 int32, optional: per-layer capacity-
    overflow shed switches for the MoE layers (see
    :func:`~repro.models.dispatch.build_dispatch`). A *scanned operand*
    like the placements, so per-step shed decisions never retrace the
    compiled decode executable; ``None`` (the default) keeps the traced
    program byte-identical to the pre-shed step.
    """
    x = embed_tokens(tokens, params["embed"], config, policy)
    x = policy.act_bsd(x)
    blocks = params["blocks"]
    moe_aux = None
    if block_tables is not None and (config.is_ssm or config.is_hybrid):
        raise ValueError("paged decode requires an attention-family arch")

    if config.is_hybrid:
        staged, leftover = _hybrid_split(config)
        n_stages = staged // config.attn_every
        shared = params["shared"]
        sp = _slice_layer(shared, 0)

        def stage_body(xc, inputs):
            stage_blocks, ssm_c, attn_c = inputs

            def inner(xc2, inp):
                lp, cache_t = inp
                h = rms_norm(xc2, lp["ln"], config.norm_eps)
                y, new_c = ssm_decode(
                    h, lp["ssm"], SSMCache.from_tree(cache_t), config, policy
                )
                return xc2 + y, new_c.tree()

            xc, new_ssm = _scan_or_unroll(
                inner, xc, (stage_blocks, ssm_c), decode_mode
            )
            h = rms_norm(xc, sp["ln1"], config.norm_eps)
            a, new_attn = attention_decode(
                h, sp["attn"], AttnCache(attn_c["k"], attn_c["v"]), cur_len,
                config, policy,
            )
            xc = xc + a
            h2 = rms_norm(xc, sp["ln2"], config.norm_eps)
            y = gated_mlp(
                h2, sp["mlp"], activation=config.mlp_activation, policy=policy
            )
            return xc + y, (new_ssm, {"k": new_attn.k, "v": new_attn.v})

        staged_blocks = jax.tree.map(
            lambda t: t[:staged].reshape(n_stages, config.attn_every, *t.shape[1:]),
            blocks,
        )
        x, (new_ssm, new_attn) = _scan_or_unroll(
            stage_body, x, (staged_blocks, _ssm_xs(caches["ssm_staged"]),
                            caches["attn"]), decode_mode
        )
        new_caches = {"ssm_staged": _ssm_named(new_ssm), "attn": new_attn}
        if leftover:
            tail_blocks = jax.tree.map(lambda t: t[staged:], blocks)

            def tail(xc, inp):
                lp, cache_t = inp
                h = rms_norm(xc, lp["ln"], config.norm_eps)
                y, new_c = ssm_decode(
                    h, lp["ssm"], SSMCache.from_tree(cache_t), config, policy
                )
                return xc + y, new_c.tree()
            x, new_tail = _scan_or_unroll(
                tail, x, (tail_blocks, _ssm_xs(caches["ssm_tail"])), decode_mode
            )
            new_caches["ssm_tail"] = _ssm_named(new_tail)
    elif config.is_ssm:
        def body(xc, inp):
            lp, cache_t = inp
            h = rms_norm(xc, lp["ln"], config.norm_eps)
            y, new_c = ssm_decode(
                h, lp["ssm"], SSMCache.from_tree(cache_t), config, policy
            )
            return xc + y, new_c.tree()
        x, new_ssm = _scan_or_unroll(
            body, x, (blocks, _ssm_xs(caches["ssm"])), decode_mode
        )
        new_caches = {"ssm": _ssm_named(new_ssm)}
    else:
        if placements is None:
            placements = identity_placement(config, config.num_layers)

        # named scopes split a profile of the compiled step by layer part;
        # what is left directly under "layer_scan" is the scan's own
        # operand and carry handling
        def ffn_half(xc, lp, placement_l, shed_l, layer=None, experts=None):
            # experts: the stacked expert weights, read at ``layer`` in
            # place; None when ``lp`` holds this layer's own
            with jax.named_scope("ffn"):
                h2 = rms_norm(xc, lp["ln2"], config.norm_eps)
                if config.is_moe:
                    y, aux = moe_layer(
                        h2, {**lp["moe"], **(experts or {})}, placement_l,
                        config, policy,
                        capacity_factor=config.decode_capacity_factor,
                        shed_enable=shed_l, layer=layer,
                    )
                else:
                    aux = _moe_aux_zero(config) if config.is_moe else 0.0
                    y = gated_mlp(
                        h2, lp["mlp"], activation=config.mlp_activation,
                        policy=policy,
                    )
                if config.is_moe and aux is None:
                    aux = _moe_aux_zero(config)
                xc = xc + y
            return xc, aux

        # shed_enables=None is an empty pytree node: the scanned operands
        # (and therefore every compiled decode executable) are then the
        # pre-shed ones
        layer_ops = (blocks, placements, shed_enables)

        if block_tables is not None:
            # the stacked pools ride in the carry and each layer reads and
            # writes its own in place through the scanned layer index: no
            # layer's pool is sliced out of the stack or written back. The
            # expert weights stay stacked as well, closed over by the body,
            # and the expert kernel reads each layer's in place
            experts = None
            if config.is_moe:
                moe_blocks, experts = _split_stacked_experts(blocks)
                layer_ops = (moe_blocks, placements, shed_enables)

            def body(carry, inputs):
                xc, k_pool, v_pool = carry
                (lp, placement_l, shed_l), layer = inputs
                with jax.named_scope("attention"):
                    h = rms_norm(xc, lp["ln1"], config.norm_eps)
                    a, (k_pool, v_pool) = attention_decode_paged(
                        h, lp["attn"], k_pool, v_pool, layer, block_tables,
                        cur_len, config, policy,
                    )
                    xc = xc + a
                xc, aux = ffn_half(xc, lp, placement_l, shed_l, layer, experts)
                return (xc, k_pool, v_pool), aux

            carry = (x, caches["attn"]["k"], caches["attn"]["v"])
            xs = (layer_ops, jnp.arange(config.num_layers, dtype=jnp.int32))
            with jax.named_scope("layer_scan"):
                (x, k_pool, v_pool), auxes = _scan_or_unroll(
                    body, carry, xs, decode_mode
                )
            new_caches = {"attn": {"k": k_pool, "v": v_pool}}
        else:
            def body(xc, inputs):
                (lp, placement_l, shed_l), cache = inputs
                with jax.named_scope("attention"):
                    h = rms_norm(xc, lp["ln1"], config.norm_eps)
                    a, new_c = attention_decode(
                        h, lp["attn"], AttnCache(cache["k"], cache["v"]),
                        cur_len, config, policy,
                    )
                    xc = xc + a
                xc, aux = ffn_half(xc, lp, placement_l, shed_l)
                return xc, ({"k": new_c.k, "v": new_c.v}, aux)

            xs = (layer_ops, caches["attn"])
            with jax.named_scope("layer_scan"):
                x, (new_attn, auxes) = _scan_or_unroll(
                    body, x, xs, decode_mode
                )
            new_caches = {"attn": new_attn}
        if config.is_moe:
            moe_aux = auxes

    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], config.norm_eps)
        logits = lm_logits(x, params, config, policy, mode="decode")
    return logits[:, 0], new_caches, moe_aux


def _ssm_xs(named):
    return (named["state"], named["conv_x"], named["conv_b"], named["conv_c"])


def _ssm_named(tree_tuple):
    s, cx, cb, cc = tree_tuple
    return {"state": s, "conv_x": cx, "conv_b": cb, "conv_c": cc}
