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
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.kernels.moe_gemm import SKINNY_BLOCK_C, moe_ffn_pallas
from repro.kernels.topk_router import topk_router_pallas
from repro.launch.hlo_analysis import (
    aliased_parameters,
    arrays_shaped,
    expert_weight_shapes,
)
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


# the serving cell's paged pool: 32 slots of 1296 tokens in 16-token
# blocks, 81 table entries a slot, and the null block
CELL_SLOTS, CELL_BLOCK, CELL_N_MAX = 32, 16, 1296 // 16
CELL_BLOCKS = 1 + CELL_SLOTS * CELL_N_MAX
# one layer's K or V pool, lane-dense: (N, block_size, KV·hd)
POOL_LAYER = (CELL_BLOCKS, CELL_BLOCK, GRANITE.num_kv_heads * GRANITE.head_dim)
# instructions that move a pool or one layer of it rather than write a token
POOL_MOVES = ("copy", "copy-start", "copy-done", "dynamic-slice",
              "dynamic-update-slice")


def _granite_decode(backend: str, sharding):
    """The full-width bf16 granite decode step (scan mode) compiled for
    ``sharding``: one described chip with the serving cell's paged pool,
    donated as the engine donates it, or a (1, 4) mesh of them with 8
    slots over a dense 640-token cache (the paged pool does not shard)."""
    cfg = dataclasses.replace(GRANITE, moe_backend=backend)
    Ev = cfg.num_experts * cfg.expert_tp
    if isinstance(sharding, Mesh):
        B = 8
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
    B, n_max = CELL_SLOTS, CELL_N_MAX
    params, _ = abstract_params(cfg, policy, jnp.bfloat16)
    caches = jax.eval_shape(lambda: init_paged_decode_cache(
        cfg, CELL_BLOCKS, CELL_BLOCK, policy, jnp.bfloat16))
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

    return jax.jit(paged_step, donate_argnums=(1,)).lower(*args).compile()


def test_granite_decode_step_lowers_real_kernels(one_chip, monkeypatch):
    """The pallas decode step compiles with Mosaic kernels in it.
    ``auto_interpret`` reads the host backend, so without the patch the
    kernels would be lowered interpreted."""
    monkeypatch.setattr("repro.models.dispatch.auto_interpret", lambda: False)
    assert "tpu_custom_call" in _granite_decode("pallas", one_chip).as_text()


def test_granite_paged_decode_writes_the_pool_in_place(one_chip, monkeypatch):
    """The paged decode carries the stacked K/V pools through its layer
    scan and scatters each token into them: no instruction copies or
    slices out a layer's pool, and both donated pools alias the step's
    output pools, so none is copied out and back on a step."""
    monkeypatch.setattr("repro.models.dispatch.auto_interpret", lambda: False)
    text = _granite_decode("pallas", one_chip).as_text()
    assert arrays_shaped(text, "bf16", [POOL_LAYER])  # the pools are found
    assert arrays_shaped(text, "bf16", [POOL_LAYER], opcodes=POOL_MOVES) == []
    aliased = aliased_parameters(text)
    assert len(arrays_shaped("\n".join(aliased), "bf16", [POOL_LAYER])) == 2


# instructions that would hold one layer's expert weights taken out of the
# stack: a slice, a copy, or a fusion that slices
WEIGHT_MOVES = ("fusion", "dynamic-slice", "slice", "copy", "copy-start",
                "copy-done")


def test_granite_paged_decode_reads_expert_weights_in_place(one_chip,
                                                            monkeypatch):
    """The paged decode hands the expert kernel the stacked weights and
    the layer index: no instruction slices or copies one layer's expert
    weights out of the stack. Sliced out, they cost a full extra read and
    write of every expert weight a step, and the compiler may stage one
    of the slices in VMEM ahead of the kernel."""
    monkeypatch.setattr("repro.models.dispatch.auto_interpret", lambda: False)
    text = _granite_decode("pallas", one_chip).as_text()
    shapes = expert_weight_shapes(GRANITE, 1)
    assert arrays_shaped(text, "bf16", shapes)  # the stacks are found
    assert arrays_shaped(text, "bf16", shapes, opcodes=WEIGHT_MOVES) == []


def test_arrays_shaped_finds_expert_weights_sliced_out_of_a_scan(one_chip):
    """The check above can fail: a scan that takes the stacked weights as
    scanned operands and gives the kernel each layer's own has them
    sliced out, and that is found."""
    E, D, F = expert_weight_shapes(GRANITE, 1)[0]
    bf16 = jnp.bfloat16

    def step(x, wg, wu, wd):
        def body(carry, w):
            return carry, moe_ffn_pallas(x, *w, block_c=SKINNY_BLOCK_C,
                                         block_f=F)
        return jax.lax.scan(body, 0, (wg, wu, wd))[1]

    text = jax.jit(step).lower(
        _shape(one_chip, (E, SKINNY_BLOCK_C, D), bf16),
        _shape(one_chip, (2, E, D, F), bf16),
        _shape(one_chip, (2, E, D, F), bf16),
        _shape(one_chip, (2, E, F, D), bf16),
    ).compile().as_text()
    assert arrays_shaped(text, "bf16", [(E, D, F)], opcodes=WEIGHT_MOVES)


def test_arrays_shaped_finds_a_pool_layer_moved_through_a_scan(one_chip):
    """The check above can fail: a scan that takes the stacked pool as a
    scanned operand, writes a token into each layer's slice and stacks the
    slices back as its output has a layer's pool sliced out and written
    back, and that is found."""
    bf16 = jnp.bfloat16

    def step(pool, row):
        def body(carry, layer):
            return carry, layer.at[0, 0].set(row)
        return jax.lax.scan(body, 0, pool)[1]

    text = jax.jit(step).lower(
        _shape(one_chip, (4, *POOL_LAYER), bf16),
        _shape(one_chip, POOL_LAYER[-1:], bf16),
    ).compile().as_text()
    assert arrays_shaped(text, "bf16", [POOL_LAYER], opcodes=POOL_MOVES)


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


# the mixtral-8x7b-4l benchmark configuration: published widths, four
# whole layers, no window (none binds at the cell's 1296 positions), the
# Pallas backend and dropless capacity factors of E/k
MIXTRAL_4L = dataclasses.replace(
    MIXTRAL, num_layers=4, sliding_window=0, moe_backend="pallas",
    capacity_factor=4.0, decode_capacity_factor=4.0)
# one v5e chip's memory
CHIP_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def mixtral_programs(one_chip):
    """The 4-layer mixtral prefill, paged decode and migration apply,
    compiled once for the module with the Mosaic kernels lowered."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("repro.models.dispatch.auto_interpret", lambda: False)
        return {w: _mixtral_program(one_chip, w)
                for w in ("prefill", "decode", "migrate")}


def _mixtral_program(one_chip, which):
    """A 4-layer mixtral program compiled for one described chip, with the
    weights (and the paged pool where it is an argument) as arguments.
    Returns (compiled, bytes of the cell's arguments it does not take)."""
    from repro.kernels.collective import MigrationExecutable
    from repro.models.model import prefill

    cfg, policy = MIXTRAL_4L, host_policy()
    Ev = cfg.num_experts * cfg.expert_tp
    params, _ = abstract_params(cfg, policy, jnp.bfloat16)
    caches = jax.eval_shape(lambda: init_paged_decode_cache(
        cfg, CELL_BLOCKS, CELL_BLOCK, policy, jnp.bfloat16))
    on_chip = functools.partial(jax.tree.map,
                                lambda s: _shape(one_chip, s.shape, s.dtype))
    params, caches = on_chip(params), on_chip(caches)
    tables = _shape(one_chip, (cfg.num_layers, Ev), jnp.int32)
    nbytes = lambda tree: sum(s.size * s.dtype.itemsize  # noqa: E731
                              for s in jax.tree.leaves(tree))
    if which == "prefill":
        tokens = _shape(one_chip, (1, 1024), jnp.int32)
        step = jax.jit(lambda p, t, pl: prefill(p, {"tokens": t}, cfg,
                                                policy, pl))
        return step.lower(params, tokens, tables).compile(), nbytes(caches)
    if which == "decode":
        B, n_max = CELL_SLOTS, CELL_N_MAX
        step = jax.jit(
            lambda p, c, cl, tb, tk, pl: decode_step(
                p, c, cl, tk, cfg, policy, pl, block_tables=tb),
            donate_argnums=(1,))
        return step.lower(
            params, caches, _shape(one_chip, (B,), jnp.int32),
            _shape(one_chip, (B, n_max), jnp.int32),
            _shape(one_chip, (B, 1), jnp.int32), tables).compile(), 0
    # the engine's apply: the three stacks donated, the router tables
    # swapped in the same program
    moe = params["blocks"]["moe"]
    ex = MigrationExecutable()
    apply = jax.jit(ex._host_apply, donate_argnums=(2, 3, 4))
    rest = nbytes(params) - nbytes([moe[n] for n in ("w_gate", "w_up",
                                                     "w_down")])
    return apply.lower(tables, tables, moe["w_gate"], moe["w_up"],
                       moe["w_down"]).compile(), rest + nbytes(caches)


# one layer of one expert weight, in bytes
MIXTRAL_LAYER_WEIGHT = (MIXTRAL.num_experts * MIXTRAL.d_model
                        * MIXTRAL.expert_d_ff * 2)


def test_mixtral_prefill_reads_expert_weights_in_place(mixtral_programs):
    """The 1024-token prefill hands the expert kernel the stacked weights
    and the scanned layer index: no instruction slices or copies one
    layer's (16, 4096, 7168) weights out of the stack, where each would be
    a 0.94 GB temporary and a full extra read and write a prefill."""
    compiled, _ = mixtral_programs["prefill"]
    text = compiled.as_text()
    shapes = expert_weight_shapes(MIXTRAL_4L, 1)
    assert "tpu_custom_call" in text
    assert arrays_shaped(text, "bf16", shapes)  # the stacks are found
    assert arrays_shaped(text, "bf16", shapes, opcodes=WEIGHT_MOVES) == []
    assert compiled.memory_analysis().temp_size_in_bytes < (
        MIXTRAL_LAYER_WEIGHT)


def test_mixtral_migration_apply_rewrites_one_layer_at_a_time(
        mixtral_programs):
    """The migration apply gathers one layer's rows of one weight at a
    time and writes them over that layer in place: no instruction slices
    or copies a layer out of the stacks, the three donated stacks alias
    the outputs, and the temporaries hold no more than one layer of one
    weight (the whole-stack gather held 5.2 GB)."""
    compiled, _ = mixtral_programs["migrate"]
    text = compiled.as_text()
    shapes = expert_weight_shapes(MIXTRAL_4L, 1)
    moves = ("dynamic-slice", "slice", "copy", "copy-start", "copy-done")
    assert arrays_shaped(text, "bf16", shapes, opcodes=moves) == []
    aliased = aliased_parameters(text)
    assert len(arrays_shaped("\n".join(aliased), "bf16", shapes)) == 3
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= MIXTRAL_LAYER_WEIGHT * 1.01


def test_granite_migration_apply_holds_one_layer_at_most(one_chip):
    """At granite's widths too (80 small rows a layer, 32 layers) the
    apply's temporaries stay within one layer of one weight, and the
    three donated stacks alias the outputs: a one-op gather of each
    layer's rows makes XLA copy the whole stack here."""
    from repro.kernels.collective import MigrationExecutable

    Ev = GRANITE.num_experts * GRANITE.expert_tp
    params, _ = abstract_params(GRANITE, host_policy(), jnp.bfloat16)
    moe = {n: _shape(one_chip, s.shape, s.dtype)
           for n, s in params["blocks"]["moe"].items()}
    tables = _shape(one_chip, (GRANITE.num_layers, Ev), jnp.int32)
    apply = jax.jit(MigrationExecutable()._host_apply,
                    donate_argnums=(2, 3, 4))
    compiled = apply.lower(tables, tables, moe["w_gate"], moe["w_up"],
                           moe["w_down"]).compile()
    layer = moe["w_gate"].size // GRANITE.num_layers * 2
    shapes = expert_weight_shapes(GRANITE, 1)
    aliased = aliased_parameters(compiled.as_text())
    assert len(arrays_shaped("\n".join(aliased), "bf16", shapes)) == 3
    assert compiled.memory_analysis().temp_size_in_bytes <= layer * 1.01


@pytest.mark.parametrize("which", ["prefill", "decode", "migrate"])
def test_mixtral_programs_fit_one_chip_with_the_cells_pool(mixtral_programs,
                                                           which):
    """Each 4-layer program's arguments, outputs that alias none of them,
    and temporaries, beside whatever of the weights and the cell's paged
    pool it does not take, fit in one chip's memory."""
    compiled, others = mixtral_programs[which]
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes + others)
    assert held < CHIP_BYTES, held / 1e9
