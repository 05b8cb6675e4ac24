"""The trace reduction on a trace recorded on a TPU v5e chip: two decode
steps of granite-moe-3b-a800m served by the engine, inside the
benchmark's ``bench.slice`` span."""
import gzip

import numpy as np
import pytest
import readers
import xplane
from conftest import DATA


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "granite-decode.xplane.pb.gz").read_bytes())
    return xplane.reduce_profile(ProfileData.from_serialized_xspace(raw))


def test_window_and_busy(red):
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.444198311)
    assert red.busy_s == pytest.approx(0.215718323)
    assert 0 < red.busy_s < red.window_s


def test_modules_by_name(red):
    runs, seconds = red.module_time(readers.DECODE_MODULE)
    assert runs == 2
    assert seconds == pytest.approx(0.215689929)
    assert red.module_time(readers.PREFILL_MODULE) == (0, 0.0)
    # the decode program is nearly all of the busy time
    assert seconds <= red.busy_s < seconds + 1e-3


def test_kernel_time_inside_the_decode_program(red):
    kernel = red.op_time(readers.EXPERT_FFN_OP, readers.DECODE_MODULE)
    assert kernel == pytest.approx(0.017814068)
    assert red.op_time(readers.EXPERT_FFN_OP, r"_prefill_fn") == 0.0
    assert red.op_time(r"^%copy\.98\b", readers.DECODE_MODULE) > 0


def test_idle_is_labelled_by_the_open_span(red):
    gaps = red.idle_gaps(10)
    assert len(gaps) <= 10
    total = gaps[0]
    assert total[0] == "bench.step"
    assert total[1] == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
    assert all(name.startswith("longest:") for name, _ in gaps[1:])
    lengths = [s for _, s in gaps[1:]]
    assert lengths == sorted(lengths, reverse=True)


def test_top_ops_leave_out_enclosing_loops(red):
    top = red.top_ops(10)
    assert len(top) == 10
    assert top[0] == ["%moe_ffn_pallas.12", pytest.approx(0.017814068)]
    assert not any(name.startswith("%while") for name, _ in top)
    assert all(" = " not in name for name, _ in top)


def test_readers_on_the_trace(red):
    class Ctx:
        trace = red
        slice_prefill_tokens = 0
        slice_counts = []
        step_s = step_flops = 0.0

    idle = readers.device_idle_share(Ctx)
    assert idle == pytest.approx(100 * (1 - 0.215718323 / 0.444198311))
    assert readers.decode_step_ms(Ctx) == pytest.approx(107.8449645)
    assert readers.prefill_ms_per_ktok(Ctx) is None
    assert readers.expert_ffn_roofline(Ctx) is None  # no counts recorded
    assert readers.step_mfu(Ctx) is None


def test_roofline_share_from_counts(red):
    import json

    import reference
    import work
    from conftest import BENCH

    cfg = json.loads((BENCH / "configs" / "granite-moe-3b-a800m.json").read_text())

    class Ctx:
        trace = red
        dims = reference.dims(cfg)
        peak = work.peaks("TPU v5 lite")
        # 32 busy slots, 8 experts each, spread evenly: 256 of 40 experts
        slice_counts = [np.full((32, 40), 32 * 8 // 40 + 1)] * 2

    share = readers.expert_ffn_roofline(Ctx)
    least = work.expert_ffn_least_s(Ctx.dims, np.stack(Ctx.slice_counts), Ctx.peak)
    assert share == pytest.approx(100 * least / 0.017814068)
    assert 0 < share < 100
