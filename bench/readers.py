"""The arithmetic behind the per-layer metrics in ``bench/metrics/``.

Each reader takes the run's :class:`~cell.LayerContext` and returns a
number, or ``None`` where the run gave it nothing to read; the harness then
leaves the metric out of the result line.
"""
from __future__ import annotations

import numpy as np

from work import expert_ffn_least_s

# the engine's jitted programs, by the names of the functions they trace
DECODE_MODULE = r"_decode_paged"
PREFILL_MODULE = r"_prefill_fn"
# the Pallas expert FFN kernel: its custom call is named after the jitted
# kernels/moe_gemm.py::moe_ffn_pallas
EXPERT_FFN_OP = r"^%?moe_ffn_pallas\b"


def device_idle_share(ctx):
    """Per cent of the traced slice in which no operation ran on the chip."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)


def prefill_ms_per_ktok(ctx):
    """Device milliseconds of the prefill programs per thousand prompt
    tokens prefilled in the traced slice."""
    if ctx.trace is None or ctx.slice_prefill_tokens == 0:
        return None
    runs, seconds = ctx.trace.module_time(PREFILL_MODULE)
    if runs == 0:
        return None
    return seconds * 1e3 / (ctx.slice_prefill_tokens / 1e3)


def decode_step_ms(ctx):
    """Device milliseconds of one run of the decode program."""
    if ctx.trace is None:
        return None
    runs, seconds = ctx.trace.module_time(DECODE_MODULE)
    if runs == 0:
        return None
    return seconds * 1e3 / runs


def expert_ffn_roofline(ctx):
    """Per cent of its roofline that the expert FFN kernel reached inside
    the decode program: the least time of the work the router sent it
    (``work.expert_ffn_least_s`` over the traced steps' expert counts)
    over the kernel's device time."""
    if ctx.trace is None or not ctx.slice_counts:
        return None
    kernel_s = ctx.trace.op_time(EXPERT_FFN_OP, DECODE_MODULE)
    if kernel_s <= 0:
        return None
    counts = np.stack(ctx.slice_counts)
    return 100.0 * expert_ffn_least_s(ctx.dims, counts, ctx.peak) / kernel_s


def step_mfu(ctx):
    """Per cent of the chip's peak FLOP rate reached by the window's engine
    steps: the useful FLOPs of every prompt they prefilled and every token
    they decoded (``work.py``) over the wall time spent inside ``step()``."""
    if ctx.step_s <= 0 or ctx.step_flops <= 0:
        return None
    return 100.0 * ctx.step_flops / (ctx.step_s * ctx.peak["flops"])
