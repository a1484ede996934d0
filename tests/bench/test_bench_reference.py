"""The Qwen3 family's plain float32 reference against the served path: chunked prefill
through mixed steps, then decode through the paged KV cache, with every GEMM
unquantized and the whole program in float32, on the benchmark's seeded
weights. The served greedy tokens must be the reference's best at every
position; a reference with one detail of the block changed must not agree."""

import dataclasses

import jax
import numpy as np
import pytest

import bench.weights
from bench import run
from bench_smoke import SMOKE_STD, config


def _served(monkeypatch, seed: int):
    """(prompt, served tokens) of a few requests through the Scheduler."""
    from repro.models import abstract_params
    from repro.serve import Request, Scheduler

    monkeypatch.setattr(bench.weights, "STD", SMOKE_STD)
    cfgf = config()
    cfg = run.model_config(cfgf)
    rc = dataclasses.replace(run.run_config(cfgf, "*=bf16"), dtype="float32",
                             param_dtype="float32")
    params = bench.weights.make_weights(abstract_params(cfg, rc), seed)
    sv = cfgf["serving"]
    sched = Scheduler(cfg, rc, params, capacity=sv["capacity"], max_batch=sv["max_batch"],
                      num_pages=sv["num_pages"])
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n).tolist(), max_new=m)
            for i, (n, m) in enumerate([(5, 9), (37, 6), (70, 12), (16, 3)])]
    for r in reqs:
        sched.submit(r)
    sched.run()
    return cfgf, params, [(r.prompt, r.out) for r in reqs]


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_served_tokens_are_the_references_best(monkeypatch, seed):
    cfgf, params, served = _served(monkeypatch, seed)
    reference = run.family(cfgf)
    cap = cfgf["serving"]["capacity"]
    for prompt, out in served:
        gaps = reference.logit_gaps(params, cfgf, prompt, out, cap)
        assert len(gaps) == len(out)
        assert gaps.max() < 1e-4, gaps


def test_a_changed_block_disagrees(monkeypatch):
    cfgf, params, served = _served(monkeypatch, 3)
    reference = run.family(cfgf)
    wrong = dataclasses.replace(reference.Dims.of(cfgf), theta=1e4)   # RoPE base
    monkeypatch.setattr(reference.Dims, "of", classmethod(lambda cls, config: wrong))
    cap = cfgf["serving"]["capacity"]
    worst = max(reference.logit_gaps(params, cfgf, p, o, cap).max() for p, o in served)
    assert worst > 0.1


def test_weights_repeat_from_the_seed_and_differ_above_bit_32():
    from repro.models import abstract_params

    cfgf = config()
    abstract = abstract_params(run.model_config(cfgf), run.run_config(cfgf, "*=bf16"))
    a, b = (bench.weights.make_weights(abstract, 5) for _ in range(2))
    c = bench.weights.make_weights(abstract, 5 + 2**32)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
    assert all(x.dtype == np.dtype("bfloat16") for x in la)
