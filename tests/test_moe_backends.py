"""MoE kernel-backend dispatch: einsum / pallas / dense_ref must agree, and
the pallas path must stay placement-invariant (the whole point of GEM's
expert swap is that the data plane is a pure permutation)."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MOE_BACKENDS, get_smoke_config
from repro.core import Placement
from repro.models.moe import (
    apply_placement,
    identity_placement,
    init_moe,
    moe_layer,
    moe_layer_dense_ref,
    resolve_moe_backend,
)
from repro.sharding import host_policy


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x7b"), capacity_factor=8.0
    )
    policy = host_policy()
    params, _ = init_moe(
        jax.random.PRNGKey(0), cfg, num_layers=1, dtype=jnp.float32,
        policy=policy,
    )
    lp = jax.tree.map(lambda t: t[0], params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    return cfg, policy, lp, x


def _gem_permuted(cfg, lp, trial=0):
    """A non-identity GEM placement + the permuted weights for it."""
    Ev = cfg.num_experts * cfg.expert_tp
    rng = np.random.default_rng(17 + trial)
    e2d = rng.permutation(
        np.repeat(np.arange(4), -(-Ev // 4))[:Ev]
    ).astype(np.int32)
    placement = Placement(e2d, 4)
    s2e = jnp.asarray(placement.slot_to_expert()[None])
    lp_perm = jax.tree.map(
        lambda t: t[0],
        apply_placement(jax.tree.map(lambda t: t[None], lp), s2e),
    )
    lp_perm["router"] = lp["router"]
    return lp_perm, jnp.asarray(placement.expert_to_slot())


@pytest.mark.parametrize("backend", ["pallas", "dense_ref"])
def test_backend_matches_einsum(setup, backend):
    cfg, policy, lp, x = setup
    table = identity_placement(cfg, 1)[0]
    y_ref, aux_ref = moe_layer(x, lp, table, cfg, policy, backend="einsum")
    y, aux = moe_layer(x, lp, table, cfg, policy, backend=backend)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(
        np.asarray(aux["expert_counts"]), np.asarray(aux_ref["expert_counts"])
    )


def test_pallas_parity_under_gem_placement(setup):
    """Acceptance: pallas matches einsum to ≤1e-4 under a non-identity
    placement (fp32, interpret mode)."""
    cfg, policy, lp, x = setup
    table = identity_placement(cfg, 1)[0]
    y_ref, _ = moe_layer(x, lp, table, cfg, policy, backend="einsum")
    for trial in range(3):
        lp_perm, e2s = _gem_permuted(cfg, lp, trial)
        y, _ = moe_layer(x, lp_perm, e2s, cfg, policy, backend="pallas")
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4
        )


def test_pallas_placement_invariance(setup):
    """Within the pallas backend, permuting weights+tables is a no-op."""
    cfg, policy, lp, x = setup
    table = identity_placement(cfg, 1)[0]
    y0, aux0 = moe_layer(x, lp, table, cfg, policy, backend="pallas")
    lp_perm, e2s = _gem_permuted(cfg, lp)
    y1, aux1 = moe_layer(x, lp_perm, e2s, cfg, policy, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(y0), np.asarray(y1), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(aux0["expert_counts"]), np.asarray(aux1["expert_counts"])
    )


def test_dense_ref_placement_invariance(setup):
    """Regression: dense_ref must gather the slot-ordered weights back to
    virtual-expert order, or any non-identity placement silently mixes the
    wrong experts."""
    cfg, policy, lp, x = setup
    table = identity_placement(cfg, 1)[0]
    y0, _ = moe_layer(x, lp, table, cfg, policy, backend="dense_ref")
    lp_perm, e2s = _gem_permuted(cfg, lp)
    y1, _ = moe_layer(x, lp_perm, e2s, cfg, policy, backend="dense_ref")
    np.testing.assert_allclose(
        np.asarray(y0), np.asarray(y1), rtol=1e-5, atol=1e-5
    )


def test_dense_ref_backend_matches_oracle(setup):
    cfg, policy, lp, x = setup
    table = identity_placement(cfg, 1)[0]
    y, aux = moe_layer(x, lp, table, cfg, policy, backend="dense_ref")
    y_oracle = moe_layer_dense_ref(x, lp, cfg)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_oracle), rtol=1e-6, atol=1e-6
    )
    assert float(aux["dropped"]) == 0.0


def test_config_backend_is_used(setup):
    """moe_backend set on the config (no explicit kwarg) reaches dispatch."""
    cfg, policy, lp, x = setup
    cfg_pallas = dataclasses.replace(cfg, moe_backend="pallas")
    table = identity_placement(cfg, 1)[0]
    y_ref, _ = moe_layer(x, lp, table, cfg, policy)
    y, _ = moe_layer(x, lp, table, cfg_pallas, policy)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4
    )


def test_unknown_backend_rejected(setup):
    cfg, policy, lp, x = setup
    with pytest.raises(ValueError, match="moe_backend"):
        moe_layer(
            x, lp, identity_placement(cfg, 1)[0], cfg, policy,
            backend="triton",
        )
    with pytest.raises(ValueError, match="moe_backend"):
        dataclasses.replace(cfg, moe_backend="triton")
    assert set(MOE_BACKENDS) == {"einsum", "pallas", "dense_ref"}


def test_pallas_capacity_staircase_padding(setup):
    """Capacities that aren't a block multiple pad up inside the kernel and
    slice back — results identical to einsum at the unpadded capacity."""
    cfg, policy, lp, x = setup
    cfg_odd = dataclasses.replace(
        cfg, capacity_factor=3.3, pallas_block_c=8, pallas_block_f=32
    )
    table = identity_placement(cfg, 1)[0]
    y_ref, aux_ref = moe_layer(x, lp, table, cfg_odd, policy, backend="einsum")
    y, aux = moe_layer(x, lp, table, cfg_odd, policy, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4
    )
    assert float(aux["dropped"]) == float(aux_ref["dropped"])


def test_mesh_keeps_pallas():
    """Under a real mesh the pallas backend stays pallas — the per-shard
    shard_map dispatch landed; no einsum downgrade, no warning."""
    from jax.sharding import Mesh
    from repro.sharding.policy import ShardingPolicy

    cfg = get_smoke_config("mixtral-8x7b")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    policy = ShardingPolicy(mesh=mesh)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert resolve_moe_backend("pallas", cfg, policy) == "pallas"


def test_pallas_runs_under_mesh():
    """moe_layer with backend='pallas' executes the shard_map kernel path
    under a (1, 1) host mesh and matches einsum."""
    from jax.sharding import Mesh
    from repro.sharding.policy import ShardingPolicy

    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x7b"), capacity_factor=8.0
    )
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    policy = ShardingPolicy(mesh=mesh)
    params, _ = init_moe(
        jax.random.PRNGKey(0), cfg, num_layers=1, dtype=jnp.float32,
        policy=policy,
    )
    lp = jax.tree.map(lambda t: t[0], params)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    table = identity_placement(cfg, 1)[0]
    with mesh:
        y_ref, aux_ref = moe_layer(x, lp, table, cfg, policy, backend="einsum")
        y, aux = moe_layer(x, lp, table, cfg, policy, backend="pallas")
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_ref), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(
        np.asarray(aux["expert_counts"]), np.asarray(aux_ref["expert_counts"])
    )


def test_pallas_reads_a_stacked_layer_under_mesh():
    """Given the whole layer stack and a layer index, moe_layer's
    shard_map kernel path reads that layer's weights in place and matches
    the call on the layer's own weights."""
    from jax.sharding import Mesh
    from repro.sharding.policy import ShardingPolicy

    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x7b"), capacity_factor=8.0,
        expert_d_ff=128,
    )
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    policy = ShardingPolicy(mesh=mesh)
    params, _ = init_moe(
        jax.random.PRNGKey(0), cfg, num_layers=2, dtype=jnp.float32,
        policy=policy,
    )
    lp = jax.tree.map(lambda t: t[1], params)
    stacked = {**params, "router": lp["router"]}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    table = identity_placement(cfg, 1)[0]
    with mesh:
        y_ref, _ = moe_layer(x, lp, table, cfg, policy, backend="pallas")
        y, _ = jax.jit(lambda s, l: moe_layer(
            x, s, table, cfg, policy, backend="pallas", layer=l,
        ))(stacked, jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


def test_pallas_gradients_match_einsum(setup):
    """The pallas kernels are differentiable (custom_vjp with reference-math
    backward): grads of a scalar loss through moe_layer match einsum."""
    cfg, policy, lp, x = setup
    table = identity_placement(cfg, 1)[0]

    def loss(params, backend):
        y, aux = moe_layer(x, params, table, cfg, policy, backend=backend)
        return jnp.sum(y * y) + aux["aux_loss"]

    g_ref = jax.grad(lambda p: loss(p, "einsum"))(lp)
    g = jax.grad(lambda p: loss(p, "pallas"))(lp)
    for name in ("router", "w_gate", "w_up", "w_down"):
        np.testing.assert_allclose(
            np.asarray(g[name]), np.asarray(g_ref[name]),
            rtol=2e-4, atol=2e-4, err_msg=name,
        )


def test_gd_collapse_warns_once():
    """B % data_axis_size != 0 collapses grouping with a one-time warning
    naming the shapes."""
    from jax.sharding import Mesh
    from repro.sharding.policy import ShardingPolicy

    cfg = dataclasses.replace(
        get_smoke_config("mixtral-8x7b"), capacity_factor=8.0
    )
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    policy = ShardingPolicy(mesh=mesh)
    # pretend the data axis is 2-wide so B=3 doesn't divide it
    params, _ = init_moe(
        jax.random.PRNGKey(0), cfg, num_layers=1, dtype=jnp.float32,
        policy=host_policy(),
    )
    lp = jax.tree.map(lambda t: t[0], params)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, cfg.d_model))

    class TwoWide(ShardingPolicy):
        @property
        def data_axis_size(self):
            return 2

    policy2 = TwoWide(mesh=mesh)
    # (_WARNED starts empty each test: autouse fixture in conftest.py)
    with pytest.warns(RuntimeWarning, match=r"B=3.*Gd=2"):
        moe_layer(x, lp, identity_placement(cfg, 1)[0], cfg, policy2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        moe_layer(x, lp, identity_placement(cfg, 1)[0], cfg, policy2)
