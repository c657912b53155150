"""The trace reduction on a synthetic trace: busy/idle union, kernel time
by name, attribution of idle gaps to host spans, and the readers built
on them."""
import numpy as np
import pytest

from chipbench import flops, tracing
from chipbench.harness import CallLog, _load_reader


def _trace():
    ev = tracing.Event
    ops = {0: [
        ev("%fusion.1 = bf16[8] fusion(x)", 0.10, 0.20, 0),
        ev("%fusion.2 = bf16[8] fusion(x)", 0.15, 0.25, 0),      # overlaps
        ev('%closed_call.3 = (f32[1]) custom-call(a), '
           'frontend_attributes={kernel_metadata={}}', 0.30, 0.35, 0),
        ev('%custom-call.9 = bf16[1] custom-call(), '
           'custom_call_target="AllocateBuffer"', 0.35, 0.355, 0),
        ev('%custom-call.8 = bf16[2] custom-call(b), '
           'custom_call_target="ConcatBitcast"', 0.355, 0.36, 0),
        ev("%fusion.4 = bf16[8] fusion(x)", 0.60, 0.71, 0),
        ev("%fusion.5 = bf16[8] fusion(x)", 0.95, 1.20, 0),      # crosses end
        ev("%while.2 = (s32[]) while(t), body=b", 0.10, 0.25, 0),  # holder
    ]}
    mods = {0: [ev("jit_cloud_verify(123)", 0.10, 0.36, 0),
                ev("jit_cloud_verify(123)", 0.60, 0.70, 0),
                ev("jit_broadcast_in_dim(7)", 0.95, 1.2, 0)]}
    spans = [tracing.Span("bench.traced", 0.0, 1.0),
             tracing.Span("bench.step", 0.05, 0.40),
             tracing.Span("bench.verify", 0.08, 0.38),
             tracing.Span("bench.wait", 0.40, 0.58),
             tracing.Span("bench.step", 0.58, 0.75),
             tracing.Span("bench.prefill", 0.59, 0.72)]
    return tracing.TraceData((0.0, 1.0), ops, mods, spans)


def test_busy_is_the_union_clipped_to_the_window():
    tr = _trace()
    iv = tr.busy_intervals(0)
    np.testing.assert_allclose(iv, [[0.10, 0.25], [0.30, 0.36],
                                    [0.60, 0.71], [0.95, 1.0]])
    assert tr.busy_s == pytest.approx(0.15 + 0.06 + 0.11 + 0.05)
    assert tr.window_s == pytest.approx(1.0)


def test_kernel_time_by_name_and_program_time_by_span():
    tr = _trace()
    verify = tr.spans_named("bench.verify")[0]
    kern = tr.ops_in(verify, tracing.is_kernel)
    assert [tracing.op_label(o.name) for o in kern] == ["closed_call.3"]
    assert sum(m.t1 - m.t0 for m in tr.modules_in(
        verify, "jit_cloud_verify")) == pytest.approx(0.26)


def test_idle_gaps_named_by_the_innermost_host_span():
    tr = _trace()
    assert tr.innermost(0.45) == tracing.SPAN_NAMES["bench.wait"]
    assert tr.innermost(0.27) == tracing.SPAN_NAMES["bench.verify"]
    assert tr.innermost(0.39) == tracing.SPAN_NAMES["bench.step"]
    bd = tr.breakdown()
    longest = bd["idle_gaps"][0]
    assert longest[1] == pytest.approx(0.24)          # 0.71 -> 0.95
    assert longest[0] == "TPU0: " + tracing.SPAN_NAMES["bench.traced"]
    names = [k for k, _ in bd["device_ops"]]
    assert names[:2] == ["jit_cloud_verify/fusion.4",
                         "jit_cloud_verify/fusion.1"]
    assert dict(bd["device_ops"])["jit_broadcast_in_dim/fusion.5"] == \
        pytest.approx(0.05)
    assert all(v > 0 for _, v in bd["device_ops"])
    assert not any("while" in k for k in names)
    assert len(bd["idle_gaps"]) <= 10 and len(bd["device_ops"]) <= 10


class _Cfg:
    n_layers, n_heads, n_kv_heads, head_dim = 2, 4, 2, 16
    d_model, d_ff, vocab = 64, 128, 256


def test_readers_on_the_synthetic_trace():
    tr = _trace()
    log = CallLog()
    pos = np.full((2, 8), -1)
    pos[0, :3] = [5, 6, 7]
    log.traced = [("verify", flops.step_flops(_Cfg, pos),
                   [flops.kernel_cost(_Cfg, pos)])]
    peak = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    ctx = dict(trace=tr, counts=log, peak=peak)
    assert _load_reader("verify_step_ms")(ctx) == pytest.approx(260.0)
    assert _load_reader("device_idle_share")(ctx) == pytest.approx(63.0)
    mfu = _load_reader("step_mfu")(ctx)
    need = flops.step_flops(_Cfg, pos)
    assert mfu == pytest.approx(100 * need / ((0.26 + 0.10) * 1e9))
    roof = _load_reader("partial_prefill_roofline")(ctx)
    f, b = flops.kernel_cost(_Cfg, pos)
    assert roof == pytest.approx(100 * max(f, b) / 1e9 / 0.05)


def test_readers_return_nothing_without_a_trace():
    ctx = dict(trace=None, counts=CallLog(), peak={})
    for name in ("verify_step_ms", "step_mfu", "partial_prefill_roofline",
                 "device_idle_share", "padded_row_share",
                 "prefix_hit_share"):
        assert _load_reader(name)(ctx) is None
