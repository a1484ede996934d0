"""``correct``: the harness's run, checked against the float32 reference, at
a size the CPU holds (tests/bench/bench_smoke.py). A sound run passes; the control
(the configuration's next lower precision, int4 GEMMs) and the faults a
serving cell can have, planted under the timed path, make it false."""


import jax.numpy as jnp
from bench_smoke import config, run_smoke


def test_sound_run_is_correct(monkeypatch):
    correct, cmp, res = run_smoke(monkeypatch)
    assert correct, cmp
    assert cmp["served_tokens_checked"]["value"] >= cmp["served_tokens_checked"]["limit"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_at_lower_precision_fails(monkeypatch):
    correct, cmp, _ = run_smoke(monkeypatch, control=True)
    assert not correct
    assert cmp["max_logit_gap"]["value"] > 3 * cmp["max_logit_gap"]["limit"]


def test_step_that_leaves_the_kv_cache_unchanged_fails(monkeypatch):
    import repro.models.attention as attention

    monkeypatch.setattr(attention, "kv_cache_write",
                        lambda cache, names, new, pos, view=None: dict(cache))
    correct, cmp, _ = run_smoke(monkeypatch)
    assert not correct and cmp["max_logit_gap"]["value"] > cmp["max_logit_gap"]["limit"]


def test_token_altered_where_it_is_produced_fails(monkeypatch):
    from repro.serve import scheduler

    emit = scheduler.Scheduler._emit
    monkeypatch.setattr(scheduler.Scheduler, "_emit",
                        lambda self, i, token: emit(self, i, (token + 1) % 256))
    correct, cmp, _ = run_smoke(monkeypatch)
    assert not correct and cmp["max_logit_gap"]["value"] > cmp["max_logit_gap"]["limit"]


def test_step_that_ignores_the_q_norm_scale_fails(monkeypatch):
    import repro.models.attention as attention

    norm, heads = attention.rms_norm, config()["num_attention_heads"]

    def q_unscaled(p, x, eps=1e-6):   # q is the norm over all query heads
        if x.ndim == 4 and x.shape[-2] == heads:
            p = {"scale": jnp.ones_like(p["scale"])}
        return norm(p, x, eps)

    monkeypatch.setattr(attention, "rms_norm", q_unscaled)
    correct, cmp, _ = run_smoke(monkeypatch)
    assert not correct and cmp["max_logit_gap"]["value"] > cmp["max_logit_gap"]["limit"]


def test_kernel_fallback_fails(monkeypatch):
    from bench import run

    qwen3 = run.family(config())
    health = {"kernels": {"paths": {n: {"xla": 3} for n in (*qwen3.GEMMS, qwen3.ATTENTION)},
                          "fallbacks": {"attn.paged": {"mesh": 1}}}}
    assert run.kernel_faults(health, "xla", qwen3) == ["fallback attn.paged: {'mesh': 1}"]
    assert any("want pallas" in e for e in run.kernel_faults(health, "pallas", qwen3))
    del health["kernels"]["paths"]["mlp.up"]
    assert "mlp.up never traced" in run.kernel_faults(health, "xla", qwen3)
