"""A CPU-sized cell for the benchmark's tests: qwen3-0.6b_smoke widths
through the harness's own run, with weights of a larger spread than the chip
cells use. At these widths normal(0, 0.02) weights leave the residual stream
dominated by the embedding, so every model, however rounded, repeats its
input token; at 0.2 the blocks dominate and rounding shows in the logits."""

from bench import run

SMOKE_STD = 0.2
# widest reference gap of a served token at these widths, int8 GEMMs: 0.07-0.19
# over three seeds; int4 GEMMs: 3.7-4.1; the q_norm scale left out: 2.5 (CPU
# runs of this file's cell)
SMOKE_LIMIT = 1.0


def config(policy_bits: int = 8) -> dict:
    c = dict(run.config_file("qwen3-0.6b"))
    c.update(name="smoke", registry="qwen3-0.6b_smoke", hidden_size=64,
             intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, vocab_size=256)
    c["serving"] = {"max_batch": 4, "prefill_chunk": 16, "capacity": 160, "num_pages": 40}
    c["kv"] = {"dtype": "bfloat16", "layout": "paged", "block_size": 16}
    c["check"] = {"max_logit_gap": SMOKE_LIMIT}
    return c


TRAFFIC = {"prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 128},
           "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
           "arrival": {"process": "poisson", "rate": 6.0},
           "warm": {"fill": 2, "seconds": 0.5}, "check_tokens": 48}


def run_smoke(monkeypatch, *, seed: int = 1, control: bool = False, seconds: float = 3.0):
    """One harness run of the smoke cell on the CPU (XLA twins), no
    persistent compilation cache; returns (correct, compared, result)."""
    import jax

    import bench.weights
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(bench.weights, "STD", SMOKE_STD)
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = config()
    res = run.run_cell("smoke", cfg, TRAFFIC, seed=seed, seconds=seconds, trace=False,
                       control=control, expect_path="xla")
    correct, cmp = run.verdict(res, cfg, TRAFFIC)
    return correct, cmp, res
