"""Observability tests (DESIGN.md §14): tracer + metrics-registry units,
health() golden keys, tracing bit-exactness (plain and speculative), kernel
counter scoping across back-to-back schedulers, structured-log formatter."""

import collections
import dataclasses
import glob
import json

import jax
import numpy as np
import pytest

from repro.configs.base import RunConfig, get_config
from repro.models import init
from repro.obs.logs import kv
from repro.obs.metrics import MetricsRegistry, family_percentile
from repro.obs.trace import (
    NULL_TRACER,
    PID_SCHED,
    Tracer,
    trace_summary,
    validate_chrome_trace,
)
from repro.serve import Request, Scheduler

RC = RunConfig(
    dtype="float32", param_dtype="float32", remat="none",
    prefill_chunk=4, kv_cache_dtype="int8", kv_layout="paged", block_size=4,
)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen3-0.6b_smoke")
    params = init(cfg, RC, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, n=4, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, 4 + 3 * i).tolist() for i in range(n)]


def _run(cfg, rc, params, *, prompts, max_new=6, **kw):
    s = Scheduler(cfg, rc, params, capacity=32, max_batch=3,
                  temperature=0.0, **kw)
    for rid, p in enumerate(prompts):
        s.submit(Request(rid=rid, prompt=list(p), max_new=max_new))
    s.run()
    return s, {r.rid: list(r.out) for r in s.finished}


# ------------------------------------------------------------------ units
def test_tracer_schema_and_summary():
    tr = Tracer()
    tr.name_process(1, "sched")
    tr.name_thread(2, 7, "req 7")
    with tr.span("tick", args={"clock": 1}):
        pass
    t0 = tr.ts()
    tr.complete("decode", 2, 7, t0, 5.0, args={"tokens": 1})
    tr.instant("submit", 2, 7)
    tr.counter("pool_pages", {"in_use": 3, "live": 5})
    obj = tr.to_dict()
    validate_chrome_trace(obj)
    s = trace_summary(obj)
    assert s["spans"] == {"tick": 1, "decode": 1}
    assert s["instants"] == {"submit": 1}
    assert s["counters"] == {"pool_pages": 1}
    assert s["request_tracks"] == 1


def test_tracer_export_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("tick"):
        pass
    p = tmp_path / "t.json"
    tr.export(str(p))
    obj = json.loads(p.read_text())
    validate_chrome_trace(obj)
    assert obj["displayTimeUnit"] == "ms"


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x"):  # must be a working (null) contextmanager
        pass
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")  # one shared null
    NULL_TRACER.instant("y", 1, 0)
    NULL_TRACER.counter("z", {"a": 1})
    assert NULL_TRACER.to_dict()["traceEvents"] == []


def test_validate_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "name": "no-ts"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})


def test_metrics_counter_gauge_histogram():
    m = MetricsRegistry()
    c = m.counter("req_total", "requests", labels=("priority",))
    c.labels("rt").inc()
    c.labels("rt").inc(2)
    c.labels("batch").inc()
    g = m.gauge("depth")
    g.value = 7
    m.gauge_fn("lazy", lambda: {"state=a": 1.0, "state=b": 2.0})
    h = m.histogram("lat_s")
    for v in (0.01, 0.02, 0.4):
        h.observe(v)
    snap = m.snapshot()
    assert snap["req_total"]["values"]["priority=rt"] == 3
    assert snap["depth"]["values"][""] == 7
    assert snap["lazy"]["values"]["state=b"] == 2.0
    assert snap["lat_s"]["values"][""]["count"] == 3
    assert h.percentile(50) == pytest.approx(0.02)
    # diff counts only deltas
    c.labels("rt").inc(5)
    d = MetricsRegistry.diff(m.snapshot(), snap)
    assert d["req_total"]["values"]["priority=rt"] == 5
    prom = m.to_prometheus()
    assert '# TYPE req_total counter' in prom
    assert 'req_total{priority="rt"} 8' in prom


def test_metrics_family_percentile():
    m = MetricsRegistry()
    h = m.histogram("x_s", labels=("k",))
    for v in (1.0, 2.0, 3.0):
        h.labels("a").observe(v)
    for v in (4.0, 5.0):
        h.labels("b").observe(v)
    assert family_percentile(h, 50) == pytest.approx(3.0)
    assert 4.5 <= family_percentile(h, 99) <= 5.0  # interpolated tail


def test_metrics_adopt_merges(tmp_path):
    a, b = MetricsRegistry(), MetricsRegistry()
    b.counter("inner_total").inc(4)
    a.adopt(b)
    assert a.snapshot()["inner_total"]["values"][""] == 4
    out = tmp_path / "m.jsonl"
    a.emit_jsonl(str(out), extra={"tag": "t"})
    a.emit_jsonl(str(out))
    lines = [json.loads(l) for l in out.read_text().splitlines()]
    assert len(lines) == 2 and lines[0]["tag"] == "t"
    assert lines[1]["metrics"]["inner_total"]["values"][""] == 4


def test_kv_formatter():
    s = kv("stall", tick=3, rid="r 1", pool=0.5)
    assert s.startswith("stall ")
    assert "tick=3" in s and "pool=0.5" in s
    assert "rid='r 1'" in s  # values with spaces are quoted


# ----------------------------------------------------- scheduler integration
def test_health_golden_keys(model):
    cfg, params = model
    s, _ = _run(cfg, RC, params, prompts=_prompts(cfg))
    h = s.health()
    for k in ("clock", "completed", "admitted", "rejections", "ladder",
              "kernels", "compiles", "latency"):
        assert k in h, f"health() lost key {k!r}"
    lat = h["latency"]
    for fam in ("ttft_s", "itl_s", "tick_s", "queue_wait_s"):
        assert set(lat[fam]) == {"count", "p50", "p95", "p99"}
        assert lat[fam]["count"] > 0
        assert lat[fam]["p50"] <= lat[fam]["p99"]
    assert "paths" in h["kernels"]


def test_kernel_counters_scoped_per_scheduler(model):
    """Regression: kernel path counters are process-global; health() must
    report only the deltas attributable to THIS scheduler instance."""
    cfg, params = model
    s1, _ = _run(cfg, RC, params, prompts=_prompts(cfg, n=2))
    k1 = s1.health()["kernels"]
    s2, _ = _run(cfg, RC, params, prompts=_prompts(cfg, n=2))
    k2 = s2.health()["kernels"]
    total1 = sum(sum(d.values()) for d in k1["paths"].values())
    total2 = sum(sum(d.values()) for d in k2["paths"].values())
    assert total1 > 0
    # same workload -> same (or fewer, jit-cached) own-counts; without
    # scoping s2 would report s1's calls on top of its own
    assert total2 <= total1


def test_compiles_scoped_per_scheduler(model):
    """Backend compiles are process-global; health() counts only those since
    THIS scheduler's construction, and a live tracer records each one."""
    cfg, params = model
    s1, _ = _run(cfg, RC, params, prompts=_prompts(cfg, n=2))
    n1 = s1.health()["compiles"]
    assert n1 > 0                      # its own jitted step at least
    tr = Tracer()
    s2 = Scheduler(cfg, RC, params, capacity=32, max_batch=3,
                   temperature=0.0, tracer=tr)
    assert s2.health()["compiles"] == 0
    jax.jit(lambda x: x * 3.0 + 1.0)(np.arange(5.0))   # one new program
    assert s2.health()["compiles"] == 1
    assert s1.health()["compiles"] == n1 + 1
    spans = [e for e in tr.to_dict()["traceEvents"] if e["name"] == "compile"]
    assert len(spans) == 1 and spans[0]["dur"] > 0 and spans[0]["pid"] == PID_SCHED


SUB_SPANS = {
    "device_step": ["step_inputs", "step_launch", "step_wait", "logits_fetch",
                    "logits_widen"],
    "commit": ["logits_check", "sample", "emit"],
}


def test_plain_tick_sub_spans_nest_in_order(model):
    cfg, params = model
    tr = Tracer()
    s, _ = _run(cfg, RC, params, prompts=_prompts(cfg), tracer=tr)
    spans = [e for e in tr.to_dict()["traceEvents"] if e.get("ph") == "X"
             and e["pid"] == PID_SCHED and e["name"] != "compile"]
    eps = 1e-3  # µs: a child's recomputed end may differ by an ulp

    def inside(p):
        return sorted((e for e in spans if e is not p
                       and p["ts"] <= e["ts"]
                       and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + eps),
                      key=lambda e: e["ts"])

    for parent, subs in SUB_SPANS.items():
        ps = [e for e in spans if e["name"] == parent]
        assert len(ps) == s.ticks > 0
        for p in ps:
            assert [e["name"] for e in inside(p)] == subs, parent
    for t in (e for e in spans if e["name"] == "tick"):
        names = [e["name"] for e in inside(t)]
        if "device_step" in names:     # a tick that ran the step
            top = [n for n in names if not any(n in v for v in SUB_SPANS.values())]
            assert top == ["admit", "plan", "cow_drain", "tables",
                           "device_step", "commit"]


def test_device_step_counts_live_page_steps(model):
    """The plain tick's device_step span carries the paged kernel's grid
    steps: ``pages_grid`` = max_batch × max_blocks and ``pages_live`` =
    sum over rows of ceil((pos + lens) / block_size). A scripted tick with
    one decode row (pos 6: 7 tokens, 2 pages), one prefill chunk (pos 0,
    4 tokens: 1 page) and one idle slot reads 3 of 3 × 8."""
    cfg, params = model
    tr = Tracer()
    s = Scheduler(cfg, RC, params, capacity=32, max_batch=3, temperature=0.0,
                  tracer=tr)
    s.submit(Request(rid=0, prompt=list(range(1, 7)), max_new=4))
    s.tick()                          # rid 0 prefills tokens 0..3
    s.tick()                          # rid 0 prefills 4..5, first token
    s.submit(Request(rid=1, prompt=list(range(1, 11)), max_new=2))
    s.tick()                          # rid 0 decodes at 6, rid 1 prefills 0..3
    events = tr.to_dict()["traceEvents"]
    step = [e for e in events if e.get("ph") == "X" and e["pid"] == PID_SCHED
            and e["name"] == "device_step"][-1]
    rows = {e["name"]: (e["args"]["pos"], e["args"]["tokens"]) for e in events
            if e.get("ph") == "X" and e["name"] in ("prefill", "decode")
            and e["ts"] == step["ts"]}
    assert rows == {"decode": (6, 1), "prefill": (0, 4)}
    assert sum(sl is None for sl in s.slots) == 1
    bs = RC.block_size
    assert step["args"]["pages_grid"] == 3 * (32 // bs) == 24
    assert step["args"]["pages_live"] == sum(
        -(-(p + n) // bs) for p, n in rows.values()) == 3


def test_phases_land_on_the_profilers_host_line(model, tmp_path):
    """Under a jax.profiler capture every scheduler-track span is also a
    serve/<name> annotation on the host line: one serve/device_step a step."""
    from jax.profiler import ProfileData

    cfg, params = model
    s = Scheduler(cfg, RC, params, capacity=32, max_batch=3, temperature=0.0,
                  tracer=Tracer())
    for rid, p in enumerate(_prompts(cfg, n=2)):
        s.submit(Request(rid=rid, prompt=p, max_new=3))
    with jax.profiler.trace(str(tmp_path)):
        s.run()
    (path,) = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(path)
    names = collections.Counter(
        e.name for plane in pd.planes if plane.name.startswith("/host")
        for ln in plane.lines for e in ln.events if e.name.startswith("serve/"))
    assert names["serve/device_step"] == s.ticks > 0
    assert names["serve/tick"] == s.clock
    for n in SUB_SPANS["device_step"] + SUB_SPANS["commit"]:
        assert names[f"serve/{n}"] == s.ticks, n


def test_tracing_changes_no_tokens_plain(model):
    cfg, params = model
    prompts = _prompts(cfg)
    _, out_off = _run(cfg, RC, params, prompts=prompts)
    tr = Tracer()
    s_on, out_on = _run(cfg, RC, params, prompts=prompts, tracer=tr,
                        track_energy=True)
    assert out_on == out_off
    obj = tr.to_dict()
    validate_chrome_trace(obj)
    summ = trace_summary(obj)
    assert summ["request_tracks"] == len(prompts)
    names = {e["name"] for e in obj["traceEvents"] if e.get("ph") == "X"}
    for n in ("tick", "admit", "plan", "device_step", "commit", "queued",
              "prefill", "decode"):
        assert n in names, f"missing span {n!r}"
    counters = {e["name"] for e in obj["traceEvents"] if e.get("ph") == "C"}
    assert {"pool_pages", "queue_depth", "ladder_level",
            "modeled_power_mw"} <= counters
    instants = {e["name"] for e in obj["traceEvents"] if e.get("ph") == "i"}
    assert {"submit", "admit", "finish"} <= instants


def test_tracing_changes_no_tokens_spec(model):
    cfg, params = model
    rc = dataclasses.replace(RC, spec_gamma=2, draft_policy="*=int2")
    prompts = _prompts(cfg, n=3)
    _, out_off = _run(cfg, rc, params, prompts=prompts)
    tr = Tracer()
    _, out_on = _run(cfg, rc, params, prompts=prompts, tracer=tr)
    assert out_on == out_off
    names = {e["name"] for e in tr.to_dict()["traceEvents"]
             if e.get("ph") == "X"}
    for n in ("draft", "verify", "device_step"):
        assert n in names, f"missing spec span {n!r}"


def test_registry_view_matches_legacy_counters(model):
    """The class-level counter properties and the registry are the same
    storage: mutating via the attribute shows up in the registry snapshot."""
    cfg, params = model
    s, out = _run(cfg, RC, params, prompts=_prompts(cfg, n=2))
    snap = s.metrics.snapshot()
    toks = sum(len(v) for v in out.values())
    assert s.generated_tokens == toks
    assert snap["serve_generated_tokens_total"]["values"][""] == toks
    assert snap["serve_ticks_total"]["values"][""] == s.ticks
    assert snap["admission_submitted_total"]["values"][""] == 2
    # prometheus export includes scheduler + admission + cache families
    prom = s.metrics.to_prometheus()
    for fam in ("serve_generated_tokens_total", "admission_submitted_total",
                "cache_pages", "serve_ttft_seconds"):
        assert fam in prom, f"{fam} missing from exposition"
