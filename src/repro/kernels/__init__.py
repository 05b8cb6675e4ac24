"""Pallas TPU kernels for GEM's compute hot-spots.

* ``moe_gemm`` — fused grouped expert FFN (the MoE layer whose tile
  staircase GEM's Step-2 profiler samples).
* ``topk_router`` — fused softmax + top-k + renorm routing.

``sharded`` holds the per-shard entry points — the same kernels run inside
``shard_map`` over the (data, model) mesh so each device computes its local
(E_v/16, C, D) shard; ``collective`` moves expert-weight rows between those
shards with ppermute (the migration plane's swap/broadcast data plane);
``compat`` builds the Mosaic compiler params and holds the per-backend
interpret default; ``ops`` wraps both kernels with that detection
(interpret=True on CPU); ``ref`` holds the pure-jnp oracles the tests
allclose against.
"""
from .collective import (
    CollectiveStats,
    apply_row_sources,
    broadcast_expert_row,
    swap_expert_rows,
)
from .compat import auto_interpret, pallas_compiler_params
from .ops import moe_ffn, moe_ffn_ref, topk_router, topk_router_ref
from .sharded import moe_ffn_sharded, topk_router_sharded

__all__ = [
    "CollectiveStats",
    "apply_row_sources",
    "auto_interpret",
    "broadcast_expert_row",
    "pallas_compiler_params",
    "moe_ffn",
    "moe_ffn_ref",
    "moe_ffn_sharded",
    "swap_expert_rows",
    "topk_router",
    "topk_router_ref",
    "topk_router_sharded",
]
