"""The chip's peaks, and the useful work of the served models.

Useful means what the model needs, never what a padded buffer holds: a
routed (token, expert) pair costs the expert's three products once, an
expert's weights are read once per call if any token reached it, and a
prompt's output head runs at its last position only. Derived from
``benchmarks/roofline.py::moe_kernel_tiles``, which counts the padded
tiles instead.
"""
from __future__ import annotations

import numpy as np

# Published peaks of one chip, keyed by JAX's device_kind. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def _layer_parts(d) -> tuple[float, float, float]:
    """FLOPs per token of one layer: (projections + router + routed experts,
    attention per position attended, output head)."""
    D, H, KV, hd = d.d_model, d.heads, d.kv_heads, d.head_dim
    proj = 2 * D * (H * hd + 2 * KV * hd) + 2 * H * hd * D
    dense = proj + 2 * D * d.experts + d.top_k * 6 * D * d.expert_ff
    per_position = 4 * H * hd  # q·k and p·v
    head = 2 * D * d.vocab
    return dense, per_position, head


def decode_flops(d, context: int) -> float:
    """One decoded token that attends to ``context`` positions, itself
    included."""
    dense, per_pos, head = _layer_parts(d)
    return d.layers * (dense + per_pos * context) + head


def prefill_flops(d, prompt_len: int) -> float:
    """A prompt of ``prompt_len`` tokens under a causal mask, with the
    output head at its last position."""
    dense, per_pos, head = _layer_parts(d)
    P = prompt_len
    return d.layers * (P * dense + per_pos * P * (P + 1) / 2) + head


def expert_ffn_least_s(d, counts, peak: dict) -> float:
    """Least time of the expert FFN calls whose per-layer per-expert token
    counts are ``counts`` (..., E): for each call the larger of its useful
    FLOPs over the peak rate and its useful bytes over the HBM bandwidth,
    summed. Bytes: the weights of every expert that received a token, and
    each routed row read in and written out once per virtual expert, in
    the served dtype (2 bytes)."""
    D, F, tp = d.d_model, d.expert_ff, d.expert_tp
    c = np.asarray(counts, np.float64).reshape(-1, d.experts)
    flops = c.sum(-1) * 6 * D * F
    weights = (c > 0).sum(-1) * 3 * D * F * 2
    rows = c.sum(-1) * tp * 2 * D * 2
    t = np.maximum(flops / peak["flops"], (weights + rows) / peak["hbm_bytes_per_s"])
    return float(t.sum())


def expert_ffn_bound(d, counts, peak: dict) -> str:
    """Which bound the calls of ``counts`` sit on, summed: "bytes" or
    "flops"."""
    D, F, tp = d.d_model, d.expert_ff, d.expert_tp
    c = np.asarray(counts, np.float64).reshape(-1, d.experts)
    flops = float(c.sum()) * 6 * D * F / peak["flops"]
    byts = float(((c > 0).sum() * 3 * D * F * 2 + c.sum() * tp * 4 * D)
                 / peak["hbm_bytes_per_s"])
    return "bytes" if byts >= flops else "flops"
