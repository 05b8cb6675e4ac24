"""The expert kernel's share of its roofline inside the prefill programs.

The work is counted over the traced slice as a whole: its prompt tokens
and its prefill runs. Every prompt of the chat mixes has at least 128
tokens, so each prefill reaches every expert of every layer and reads all
of their weights once. The least time of the aggregate is no more than
the sum of each call's least time, so the share is never overstated.
"""
from __future__ import annotations

from readers import EXPERT_FFN_OP, PREFILL_MODULE


def prefill_ffn_least_s(d, runs: int, tokens: int, peak: dict) -> float:
    """Least time of the expert FFN work of ``runs`` prefills of
    ``tokens`` prompt tokens in all: the larger of the useful FLOPs
    (6·D·F per routed pair, k pairs a token, every layer) over the peak
    rate and the useful bytes over the HBM bandwidth. Bytes: every
    expert's weights once per layer per prefill, and each routed row read
    in and written out once per virtual expert, in the served dtype (2
    bytes)."""
    D, F, L = d.d_model, d.expert_ff, d.layers
    pairs = tokens * d.top_k * L
    flops = pairs * 6 * D * F
    weights = runs * L * d.experts * 3 * D * F * 2
    rows = pairs * d.expert_tp * 2 * D * 2
    return max(flops / peak["flops"], (weights + rows) / peak["hbm_bytes_per_s"])


def prefill_ffn_roofline(ctx):
    """Per cent of its roofline that the expert FFN kernel reached inside
    the prefill programs of the traced slice."""
    if ctx.trace is None or ctx.slice_prefill_tokens == 0:
        return None
    runs, _ = ctx.trace.module_time(PREFILL_MODULE)
    kernel_s = ctx.trace.op_time(EXPERT_FFN_OP, PREFILL_MODULE)
    if runs == 0 or kernel_s <= 0:
        return None
    least = prefill_ffn_least_s(ctx.dims, runs, ctx.slice_prefill_tokens,
                                ctx.peak)
    return 100.0 * least / kernel_s
