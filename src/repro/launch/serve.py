"""Serving launcher: ``python -m repro.launch.serve --arch granite-moe-3b-a800m``.

Builds the published config in its own dtype (``--smoke`` swaps in the
tiny float32 reduction that CPU runs and tests use), places every
parameter by its sharding spec, and serves seeded requests through the
continuous-batching engine. The model runs on the local accelerator; GEM's
"devices" are a *simulated* fleet profile (staircase latency curves of
``--num-devices`` devices with ``--variability`` spread), and every latency
this launcher prints is that simulator's, not a measurement.

:func:`build_engine` is the one place an engine is assembled from a config;
``examples/serve_moe.py`` and ``chip_smoke.py`` call it too.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, ModelConfig, get_config, get_smoke_config
from ..core import (
    DeviceFleet,
    GEMConfig,
    profile_fleet,
    setup_speeds,
    simulator_measure_fn,
)
from ..core.types import VariabilityProfile
from ..models import init_params
from ..serving import EngineConfig, ServingEngine
from ..sharding import ShardingPolicy, host_policy
from .compile_cache import enable_compile_cache
from .specs import abstract_params

__all__ = [
    "build_engine",
    "init_placed_params",
    "model_config",
    "simulated_profile",
]


def model_config(arch: str, *, smoke: bool = False) -> ModelConfig:
    """The published config of ``arch``, or with ``smoke`` its tiny float32
    reduction (decode capacity raised so the tiny batches drop nothing)."""
    if smoke:
        return dataclasses.replace(
            get_smoke_config(arch), dtype="float32", decode_capacity_factor=4.0
        )
    return get_config(arch)


def init_placed_params(config: ModelConfig, policy: ShardingPolicy,
                       seed: int = 0):
    """Seeded random parameters in ``config.dtype``, each created where its
    ``init_params`` spec places it (whole on the default device without a
    mesh)."""
    dtype = jnp.dtype(config.dtype)

    def init(key):
        return init_params(config, key, policy, dtype)[0]

    shardings = None
    if policy.mesh is not None:
        shapes, _ = abstract_params(config, policy, dtype)
        shardings = jax.tree.map(lambda s: s.sharding, shapes)
    return jax.jit(init, out_shardings=shardings)(jax.random.PRNGKey(seed))


def simulated_profile(speeds, *, tile: int) -> VariabilityProfile:
    """GEM Step-2 profile of a simulated fleet with relative ``speeds``."""
    fleet = DeviceFleet.from_speeds(speeds, tile=tile, tile_time=40e-6)
    return profile_fleet(
        simulator_measure_fn(fleet), len(speeds),
        max_tokens=512, tile=tile, repeats=5,
    ).profile


def build_engine(
    config: ModelConfig,
    policy: ShardingPolicy,
    engine_config: EngineConfig,
    *,
    params=None,
    variability: str = "high",
    num_devices: int = 4,
    tile: int = 8,
    seed: int = 0,
) -> ServingEngine:
    """A :class:`ServingEngine` over ``params`` (seeded and placed by
    :func:`init_placed_params` when not given), with a simulated
    ``num_devices`` fleet profile for MoE configs."""
    if params is None:
        params = init_placed_params(config, policy, seed)
    profile = None
    if config.is_moe:
        profile = simulated_profile(
            setup_speeds(variability, num_devices), tile=tile
        )
    return ServingEngine(
        params, config, policy, engine_config,
        profile=profile, num_devices=num_devices,
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS),
                    default="granite-moe-3b-a800m")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny float32 reduction of --arch (CPU runs, tests)")
    ap.add_argument("--policy", default="gem", choices=("gem", "eplb", "linear"))
    ap.add_argument("--variability", default="high",
                    choices=("high", "moderate", "low"))
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--num-devices", type=int, default=4,
                    help="devices of the simulated GEM fleet")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = model_config(args.arch, smoke=args.smoke)
    eng = build_engine(
        cfg, host_policy(),
        EngineConfig(max_batch=8, max_len=128,
                     gem=GEMConfig(trace_length=16, num_restarts=10),
                     placement_policy=args.policy,
                     other_time_per_step=2e-4),
        variability=args.variability, num_devices=args.num_devices,
    )
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        eng.submit(rng.integers(0, cfg.vocab_size, size=int(rng.integers(8, 32))),
                   max_new_tokens=args.max_new_tokens)
    done = eng.run()
    print(f"served {len(done)} requests of {cfg.name} ({cfg.dtype}) on "
          f"{jax.devices()[0].device_kind}, {eng.step_count} steps, "
          f"replan over {args.num_devices} simulated devices "
          f"applied={eng.placement_applied}")
    print("simulated fleet latencies (fleet cost model, not measured):")
    for k, v in eng.latency_report().items():
        print(f"  {k} = {v:.6f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
