"""Production mesh construction.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches JAX device state: the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first JAX
init, smoke tests and benches see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "policy_for"]


def _make_mesh(shape, axes):
    # Auto axes keep GSPMD propagation (no explicit-sharding types)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (host-scale tests/examples)."""
    return _make_mesh((data, model), ("data", "model"))


def policy_for(mesh, *, step_kind: str, global_batch: int | None = None,
               config=None):
    """The ShardingPolicy used for a given lowered step on a given mesh.

    * Long-context decode (global_batch smaller than the data-axis extent)
      replicates the batch and shards the KV sequence over data AND model,
      so the whole fleet still participates in the cache sweep.
    * Huge models (bf16 params > ~6 GB per model-axis shard, i.e.
      internvl2-76b) also FSDP-shard parameters at inference. Decode then
      runs batch-*replicated* activations: ZeRO-sharded weights contract
      against replicated (tiny) activations with small all-reduces instead
      of per-layer multi-GB weight gathers; only the KV cache keeps its
      batch sharded over data (``cache_batch_axes``).
    """
    from ..sharding.policy import ShardingPolicy

    multi_pod = "pod" in mesh.axis_names
    batch_axes: tuple = ("pod", "data") if multi_pod else ("data",)
    kv_seq_axes: tuple = ("model",)
    cache_batch_axes = None
    fsdp = step_kind == "train"
    model_size = mesh.shape["model"]
    if config is not None and step_kind in ("decode", "prefill"):
        per_shard_gb = config.param_count() * 2 / model_size / 1024**3
        if per_shard_gb > 6.0:
            fsdp = True
            if step_kind == "decode":
                cache_batch_axes = batch_axes
                batch_axes = ()
    if step_kind == "decode" and global_batch is not None:
        data_size = 1
        for a in (cache_batch_axes or batch_axes):
            data_size *= mesh.shape[a]
        if global_batch < data_size:
            kv_seq_axes = (cache_batch_axes or batch_axes) + ("model",)
            batch_axes = ()
            cache_batch_axes = ()
    return ShardingPolicy(
        mesh=mesh,
        batch_axes=batch_axes,
        model_axis="model",
        kv_seq_axes=kv_seq_axes,
        cache_batch_axes=cache_batch_axes,
        fsdp=fsdp,
    )
