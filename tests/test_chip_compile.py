"""Compile the main path's kernels and the granite decode step for a TPU v5e.

The TPU compiler is installed here and compiles for a chip that is described,
not attached: these cases catch what interpret mode cannot — operations Mosaic
cannot lower, block shapes off the (8, 128) tiling, tiles over the scoped VMEM
budget — at the published widths. Nothing runs; a compile that passes is not a
chip run.

The topology is described inside a module-scoped fixture, never at import: only
one process may load the TPU library at a time, and under pytest-xdist every
worker imports this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.kernels.moe_gemm import SKINNY_BLOCK_C, moe_ffn_pallas
from repro.kernels.topk_router import topk_router_pallas
from repro.launch.hlo_analysis import arrays_shaped, expert_weight_shapes
from repro.launch.mesh import policy_for
from repro.launch.specs import abstract_params, cache_specs
from repro.models.model import decode_step, init_paged_decode_cache
from repro.sharding import host_policy

GRANITE = get_config("granite-moe-3b-a800m")
MIXTRAL = get_config("mixtral-8x7b")


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ffn_case(cfg, C):
    Ev = cfg.num_experts * cfg.expert_tp
    Fv = cfg.expert_d_ff // cfg.expert_tp
    return (Ev, C, cfg.d_model, Fv, min(cfg.pallas_block_c, C),
            cfg.pallas_block_f)


FFN_CASES = {
    "granite-decode-skinny": _ffn_case(GRANITE, SKINNY_BLOCK_C),
    "granite-prefill-512": _ffn_case(GRANITE, 128),
    "granite-prefill-block_c-1024": _ffn_case(GRANITE, 1024),
    "mixtral-prefill-256": _ffn_case(MIXTRAL, 256),
}


@pytest.mark.parametrize("case", sorted(FFN_CASES))
def test_moe_ffn_compiles_for_v5e(one_chip, case):
    E, C, D, F, bc, bf = FFN_CASES[case]
    bf16 = jnp.bfloat16
    compiled = jax.jit(
        lambda x, g, u, d: moe_ffn_pallas(x, g, u, d, block_c=bc, block_f=bf)
    ).lower(
        _shape(one_chip, (E, C, D), bf16),
        _shape(one_chip, (E, D, F), bf16),
        _shape(one_chip, (E, D, F), bf16),
        _shape(one_chip, (E, F, D), bf16),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("with_stats", [False, True])
@pytest.mark.parametrize("T", [8, 512])
def test_topk_router_compiles_for_v5e(one_chip, T, with_stats):
    E, k = GRANITE.num_experts, GRANITE.experts_per_token
    compiled = jax.jit(
        lambda lg: topk_router_pallas(lg, k, with_stats=with_stats)
    ).lower(_shape(one_chip, (T, E), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _granite_decode(backend: str, sharding):
    """The full-width bf16 granite decode step (scan mode) compiled for
    ``sharding``: one described chip with 8 slots over a 2048-token paged
    pool, or a (1, 4) mesh of them with a dense 640-token cache (the paged
    pool does not shard)."""
    cfg = dataclasses.replace(GRANITE, moe_backend=backend)
    Ev = cfg.num_experts * cfg.expert_tp
    B = 8
    if isinstance(sharding, Mesh):
        policy = policy_for(sharding, step_kind="decode")
        params, _ = abstract_params(cfg, policy, jnp.bfloat16)
        caches, _ = cache_specs(cfg, policy, B, 640, jnp.bfloat16)

        def rep(shape, dtype):
            return _shape(NamedSharding(sharding, P()), shape, dtype)

        def step(params, caches, cur_len, tokens, placements):
            return decode_step(params, caches, cur_len, tokens, cfg, policy,
                               placements)

        args = (params, caches, rep((), jnp.int32), rep((B, 1), jnp.int32),
                rep((cfg.num_layers, Ev), jnp.int32))
        return jax.jit(step).lower(*args).compile()
    policy = host_policy()
    block, max_len = 16, 2048
    n_max = max_len // block
    params, _ = abstract_params(cfg, policy, jnp.bfloat16)
    caches = jax.eval_shape(lambda: init_paged_decode_cache(
        cfg, 1 + B * n_max, block, policy, jnp.bfloat16))
    args = jax.tree.map(
        lambda s: _shape(sharding, s.shape, s.dtype),
        (params, caches,
         jax.ShapeDtypeStruct((B,), jnp.int32),
         jax.ShapeDtypeStruct((B, n_max), jnp.int32),
         jax.ShapeDtypeStruct((B, 1), jnp.int32),
         jax.ShapeDtypeStruct((cfg.num_layers, Ev), jnp.int32)),
    )

    def paged_step(params, caches, cur_len, tables, tokens, placements):
        return decode_step(params, caches, cur_len, tokens, cfg, policy,
                           placements, block_tables=tables)

    return jax.jit(paged_step).lower(*args).compile()


def test_granite_decode_step_lowers_real_kernels(one_chip, monkeypatch):
    """The pallas decode step compiles with Mosaic kernels in it.
    ``auto_interpret`` reads the host backend, so without the patch the
    kernels would be lowered interpreted."""
    monkeypatch.setattr("repro.models.dispatch.auto_interpret", lambda: False)
    assert "tpu_custom_call" in _granite_decode("pallas", one_chip).as_text()


@pytest.mark.parametrize("chips", [1, 4])
def test_granite_einsum_decode_keeps_expert_weights_bf16(v5e, one_chip,
                                                         chips):
    """The einsum backend computes its expert FFN with f32 operands, the
    kernel's math. The compiler must fold those upcasts into the bf16 dots:
    an f32 copy of an expert weight would double the weight traffic of a
    memory-bound decode step."""
    target = one_chip
    if chips == 4:
        target = Mesh(np.array(v5e.devices).reshape(1, 4), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
    text = _granite_decode("einsum", target).as_text()
    shapes = expert_weight_shapes(GRANITE, chips)
    assert arrays_shaped(text, "bf16", shapes)  # the weights are found
    assert arrays_shaped(text, "f32", shapes) == []


def test_arrays_shaped_finds_a_materialized_upcast(one_chip):
    """The check above can fail: an f32 copy of a weight that the program
    keeps (here it is returned beside the dot) is found."""
    E, D, F = expert_weight_shapes(GRANITE, 1)[0]
    f32, bf16 = jnp.float32, jnp.bfloat16

    def ffn(x, w):
        wf = w.astype(f32)
        return jnp.einsum("ecd,edf->ecf", x.astype(f32), wf), wf

    text = jax.jit(ffn).lower(
        _shape(one_chip, (E, 4, D), bf16), _shape(one_chip, (E, D, F), bf16)
    ).compile().as_text()
    assert arrays_shaped(text, "f32", [(E, D, F)])
