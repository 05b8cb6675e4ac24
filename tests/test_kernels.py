"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_gemm import moe_ffn_pallas
from repro.kernels.ops import moe_ffn, moe_ffn_ref, topk_router, topk_router_ref
from repro.kernels.sharded import moe_ffn_sharded

FFN_SHAPES = [
    # (E, C, D, F, block_c, block_f)
    (2, 128, 64, 256, 128, 256),
    (4, 256, 128, 512, 128, 256),
    (8, 128, 128, 256, 64, 128),
    (1, 512, 256, 512, 128, 256),
    (16, 128, 64, 128, 128, 128),
]


@pytest.mark.parametrize("E,C,D,F,bc,bf", FFN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_ffn_matches_ref(E, C, D, F, bc, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(E * 1000 + C), 4)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    wg = (jax.random.normal(ks[1], (E, D, F), dtype) * 0.05).astype(dtype)
    wu = (jax.random.normal(ks[2], (E, D, F), dtype) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (E, F, D), dtype) * 0.05).astype(dtype)
    got = moe_ffn(x, wg, wu, wd, block_c=bc, block_f=bf, interpret=True)
    want = moe_ffn_ref(x, wg, wu, wd)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_ffn_reads_a_stacked_layer_in_place(layer, dtype):
    """Given the whole (L, E, D, F) stack and a traced layer index, the
    kernel computes exactly what it computes on that layer's own weights."""
    L, E, C, D, F = 3, 4, 8, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    wg = (jax.random.normal(ks[1], (L, E, D, F), dtype) * 0.05).astype(dtype)
    wu = (jax.random.normal(ks[2], (L, E, D, F), dtype) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (L, E, F, D), dtype) * 0.05).astype(dtype)
    kw = dict(block_c=8, block_f=128, interpret=True)
    got = jax.jit(lambda l: moe_ffn_pallas(x, wg, wu, wd, l, **kw))(
        jnp.int32(layer))
    want = moe_ffn_pallas(x, wg[layer], wu[layer], wd[layer], **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("F", [256, 192])
def test_moe_ffn_sharded_reads_a_stacked_layer(F):
    """The per-group entry passes a stacked layer through to the kernel,
    and where F needs padding (192) takes that one layer out first: both
    match the call on the layer's own weights."""
    L, G, E, C, D = 2, 2, 4, 3, 128
    ks = jax.random.split(jax.random.PRNGKey(F), 4)
    x = jax.random.normal(ks[0], (G, E, C, D), jnp.float32)
    wg = jax.random.normal(ks[1], (L, E, D, F), jnp.float32) * 0.05
    wu = jax.random.normal(ks[2], (L, E, D, F), jnp.float32) * 0.05
    wd = jax.random.normal(ks[3], (L, E, F, D), jnp.float32) * 0.05
    kw = dict(mesh=None, data_spec=None, expert_spec=None, block_c=8,
              block_f=256, interpret=True)
    got = moe_ffn_sharded(x, wg, wu, wd, layer=jnp.int32(1), **kw)
    want = moe_ffn_sharded(x, wg[1], wu[1], wd[1], **kw)
    assert got.shape == want.shape == (G, E, C, D)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_moe_ffn_rejects_unaligned_capacity():
    x = jnp.zeros((2, 100, 64))
    w = jnp.zeros((2, 64, 256))
    wd = jnp.zeros((2, 256, 64))
    with pytest.raises(ValueError):
        moe_ffn(x, w, w, wd, block_c=128, interpret=True)


ROUTER_SHAPES = [
    (128, 8, 2, 128),
    (256, 40, 8, 128),
    (512, 128, 8, 256),
    (64, 16, 4, 64),
    # ragged T: padded up to a block_t multiple inside the kernel wrapper
    # (the old path silently grew the block to the full T)
    (100, 8, 2, 64),
    (130, 16, 4, 128),
]


@pytest.mark.parametrize("T,E,k,bt", ROUTER_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_router_matches_ref(T, E, k, bt, dtype):
    logits = (
        jax.random.normal(jax.random.PRNGKey(T + E), (T, E), jnp.float32) * 2
    ).astype(dtype)
    g1, i1 = topk_router(logits, k, block_t=bt, interpret=True)
    g2, i2 = topk_router_ref(logits, k)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g2), rtol=2e-5, atol=2e-5
    )


def test_topk_router_gates_normalized():
    logits = jax.random.normal(jax.random.PRNGKey(0), (128, 40))
    g, i = topk_router(logits, 8, interpret=True)
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, rtol=1e-5)
    # ids unique per token
    ids = np.asarray(i)
    for row in ids:
        assert len(set(row.tolist())) == len(row)


def test_moe_ffn_staircase_latency_model_alignment():
    """The kernel's row-block granularity is the tile the paper profiles at:
    capacity paddings below one block_c execute identical grids."""
    E, D, F = 2, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    wg = jax.random.normal(ks[1], (E, D, F)) * 0.05
    wu = jax.random.normal(ks[2], (E, D, F)) * 0.05
    wd = jax.random.normal(ks[3], (E, F, D)) * 0.05
    for C in (128, 256):
        x = jax.random.normal(ks[0], (E, C, D))
        y = moe_ffn(x, wg, wu, wd, block_c=128, block_f=128, interpret=True)
        assert y.shape == (E, C, D)
