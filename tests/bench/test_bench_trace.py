"""Trace reduction and operation/byte counting, on a trace recorded on the
chip (tests/bench/data/trace_3ticks.pbtxt: three ticks of qwen3-0.6b.chat on
one TPU v5e, trimmed to module executions, custom calls, while loops and the
longest ops)."""

from pathlib import Path

import pytest

from bench import costs, layers, run, xtrace

DATA = Path(__file__).with_name("data") / "trace_3ticks.pbtxt"
HOST_SHIFT = 3.0  # host clock = profile clock + 3 s in the synthetic records


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return xtrace.from_profile(ProfileData.from_text_proto(DATA.read_text()))


def test_modules_ops_and_annotations(trace):
    steps = [m for m in trace.modules if m.program == "jit_step"]
    assert len(steps) == 3 and len(trace.annotations) == 3
    assert sum(m.program == "jit__argmax" for m in trace.modules) == 3
    for m in steps:   # 28 layers x 7 quantized GEMMs, 28 paged attentions
        assert xtrace.kernel_calls(m, xtrace.GEMM_KERNEL) == 28 * 7
        assert xtrace.kernel_calls(m, xtrace.ATTN_KERNEL) == 28
        assert 0 < xtrace.kernel_ns(m, xtrace.ATTN_KERNEL) < m.dur
    # every module ran inside one tick annotation
    for m in trace.modules:
        assert any(a <= m.start and m.start + m.dur <= a + d for a, d in trace.annotations)


def test_self_times_leave_out_while_loops(trace):
    st = xtrace.self_times(trace.modules)
    assert not any(label.startswith("while") for _, label in st)
    step = max((m for m in trace.modules if m.program == "jit_step"), key=lambda m: m.dur)
    assert sum(ns for (name, _), ns in st.items() if name == step.name) > 0


def test_interval_arithmetic():
    assert xtrace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert xtrace.union_ns([]) == 0
    assert xtrace.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert xtrace.parse_op("%tugemm_fused_pallas.3 = bf16[4]{0} custom-call(bf16[4]{0} %x)") == (
        "tugemm_fused_pallas.3", "custom-call")
    assert xtrace.parse_op("%copy.9 = (s32[], bf16[2]) copy(bf16[2] %y)") == ("copy.9", "copy")


def test_align_recovers_the_clock_offset(trace):
    host = [a / 1e9 + HOST_SHIFT for a, _ in trace.annotations]
    assert xtrace.align(trace.annotations, host) == pytest.approx(-HOST_SHIFT * 1e9)
    with pytest.raises(ValueError):
        xtrace.align([], [])


def _records(trace):
    """Host ticks and tracer spans matching the trace: the longest step is the
    mixed one (width 128, one 128-token prefill row and 20 decode rows), the
    others decode 20 rows."""
    from types import SimpleNamespace

    longest = max(m.dur for m in trace.modules)
    ticks, spans = [], []
    for a, d in trace.annotations:
        t0, t1 = a / 1e9 + HOST_SHIFT, (a + d) / 1e9 + HOST_SHIFT
        ticks.append(SimpleNamespace(t0=t0, t1=t1))
        mixed = any(m.dur == longest for m in trace.modules if a <= m.start <= a + d)
        us = t0 * 1e6
        spans.append({"ph": "X", "name": "admit", "pid": 1, "tid": 0, "ts": us, "dur": 50.0})
        spans.append({"ph": "X", "name": "device_step", "pid": 1, "tid": 0, "ts": us + 60,
                      "dur": d / 1e3 - 500, "args": {"width": 128 if mixed else 1}})
        spans.append({"ph": "X", "name": "commit", "pid": 1, "tid": 0,
                      "ts": t1 * 1e6 - 400, "dur": 350.0})
        for r in range(20):
            spans.append({"ph": "X", "name": "decode", "pid": 2, "tid": r, "ts": us + 60,
                          "dur": 1.0, "args": {"pos": 1000 + 64 * r, "tokens": 1}})
        if mixed:
            spans.append({"ph": "X", "name": "prefill", "pid": 2, "tid": 99, "ts": us + 60,
                          "dur": 1.0, "args": {"pos": 256, "tokens": 128}})
    config = run.config_file("qwen3-0.6b")
    res = {"t_open": ticks[0].t0 - 0.001, "t_end": ticks[-1].t1 + 0.001, "ticks": ticks,
           "spans": spans, "tracer_offset_s": 0.0, "policy": config["quant_policy"],
           "recs": []}
    return res, config


def test_readers_on_the_recorded_trace(trace):
    res, config = _records(trace)
    ctx, offset, spans = layers.build(res, config, "TPU v5 lite", trace)
    assert offset == pytest.approx(-HOST_SHIFT * 1e9)
    assert [t.width for t in ctx.ticks].count(128) == 1 and len(ctx.ticks) == 3
    value = {n: run.metric_reader(n).read(ctx) for n in run.available("metrics")}
    mixed = max(m.dur for m in trace.modules)
    assert value["step_ms.mixed"] == pytest.approx(mixed / 1e6)
    assert value["tick_host_ms"] == pytest.approx(0.4)
    busy = xtrace.union_ns((m.start, m.start + m.dur) for m in trace.modules)
    assert value["device_idle_share"] == pytest.approx(100 * (1 - busy / 1e9 / ctx.window_s))
    # roofline: least time from each call's shapes over the kernel's device
    # time; every call of this mixed step computes its whole 32 x 128 rows
    m, least = 32 * 128, 0.0
    for g in run.family(config).layer_gemms(config, config["quant_policy"]):
        byts = m * g.k * 2 + g.k * g.n + m * g.n * 2 + (m + g.n) * 4
        least += 28 * costs.least_time(2.0 * m * g.k * g.n, byts, 8, ctx.peaks)
    step = next(m for m in trace.modules if m.dur == mixed)
    assert value["mixed_tugemm_roofline"] == pytest.approx(
        100 * least / (xtrace.kernel_ns(step, xtrace.GEMM_KERNEL) / 1e9))
    for name in ("mixed_tugemm_roofline", "decode_tugemm_roofline",
                 "decode_paged_attn_roofline", "step_mfu", "mixed_step_mfu",
                 "decode_step_mfu"):
        assert 0 < value[name] <= 100, name
    assert value["queue_wait_p95_s"] is None   # no requests in these records
    bd = layers.breakdown(ctx, trace, offset, spans)
    assert bd["device_ops"][0][0].startswith("step width 128: flash_paged_decode")
    assert 0 < len(bd["idle_gaps"]) <= 10


def test_gemm_and_attention_counts():
    ops = 2 * 32 * 1024 * 3072
    byts = 32 * 1024 * 2 + 1024 * 3072 + 32 * 3072 * 2 + (32 + 3072) * 4
    # a fused call priced from the shapes it ran with, as the trace gives them
    call = ("%tugemm_fused_pallas.67 = bf16[32,3072]{1,0:T(8,128)(2,1)S(1)} custom-call("
            "bf16[32,1024]{1,0:T(8,128)(2,1)} %g.833, s8[1024,3072]{1,0:T(8,128)(4,1)S(1)} %w, "
            "f32[32,1]{1,0:T(8,128)S(1)} %c.40, f32[1,3072]{1,0:T(1,128)S(1)} %s.23), "
            "custom_call_target=\"tpu_custom_call\", operand_layout_constraints={bf16[32,1024]{1,0}}")
    shapes = xtrace.parse_shapes(call)
    assert shapes[:3] == (("bf16", (32, 3072)), ("bf16", (32, 1024)), ("s8", (1024, 3072)))
    assert costs.gemm_call_cost(shapes) == (ops, byts, 8)
    # half the rows (a step that packs its tokens) is priced at half the activations
    half = tuple((t, ((16,) + d[1:]) if d[0] == 32 else d) for t, d in shapes)
    h_ops, h_bytes, _ = costs.gemm_call_cost(half)
    assert h_ops == ops / 2 and h_bytes == byts - 16 * (1024 * 2 + 3072 * 2 + 4)
    assert xtrace.parse_shapes("%copy.9 = (s32[], bf16[2]) copy(bf16[2] %y)")[1] == ("bf16", (2,))
    pk = costs.peaks("TPU v5 lite")
    assert costs.least_time(ops, byts, 8, pk) == pytest.approx(byts / 819e9)
    assert costs.bits_for("mlp.gate", "attn.*=int8,mlp.*=int4,*=bf16") == 4
    assert costs.bits_for("lm_head", "attn.*=int8,mlp.*=int4,*=bf16") == 16
    config = run.config_file("qwen3-0.6b")
    # one decode row at position 127 sees 128 keys and spans one 128-token page
    a_ops, a_bytes = costs.attn_cost(config, [(127, 1)], 128)
    assert a_ops == 4 * 16 * 128 * 128
    assert a_bytes == 128 * 2 * 8 * 128 * 2 + 2 * 16 * 128 * 2
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")


# What each reader read on these records before the program's serve/*
# annotations were kept in the trace reduction: keeping them moves nothing.
BEFORE = {
    "tick_host_ms": 0.39999999999999997,
    "step_mfu": 0.1575360826021716,
    "decode_step_mfu": 0.1370005008676837,
    "decode_tugemm_roofline": 56.12240003149196,
    "decode_paged_attn_roofline": 22.568394931840217,
    "device_idle_share": 10.518052866986704,
    "mixed_step_mfu": 0.20584907551803197,
    "mixed_tugemm_roofline": 26.322433048509758,
    "step_ms.mixed": 218.65864499999998,
}
IDLE_GAPS_BEFORE = [["tick device_step", 0.041667419999999566],
                    ["between ticks", 0.00209150999999963],
                    ["tick commit", 0.0010500000000002174],
                    ["tick, outside its phases", 0.000300000000000189],
                    ["tick admit", 0.0001499999999996504]]


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_existing_readers_read_as_before(trace, name):
    res, config = _records(trace)
    ctx, _, _ = layers.build(res, config, "TPU v5 lite", trace)
    assert run.metric_reader(name).read(ctx) == pytest.approx(BEFORE[name], rel=1e-12)


def test_breakdown_idle_gaps_as_before(trace):
    res, config = _records(trace)
    ctx, offset, spans = layers.build(res, config, "TPU v5 lite", trace)
    got = layers.breakdown(ctx, trace, offset, spans)["idle_gaps"]
    assert [k for k, _ in got] == [k for k, _ in IDLE_GAPS_BEFORE]
    assert [v for _, v in got] == pytest.approx([v for _, v in IDLE_GAPS_BEFORE], rel=1e-12)
