"""The open-loop generator: deterministic from the seed, the same work for
every seed, and a warm fill drawn from the length-biased residual."""

import numpy as np
import pytest

from bench import run, traffic

MIX = {
    "prompt": {"median": 256, "sigma": 0.6, "min": 64, "max": 1024},
    "output": {"median": 64, "sigma": 0.8, "min": 4, "max": 512},
    "arrival": {"process": "gamma", "cv": 2.0, "rate": 5.0},
    "warm": {"fill": "batch", "seconds": 2.0},
}
BIG_SEED = 2**33 + 12345


def _plan(seed, mix=MIX):
    return traffic.plan(mix, seed=seed, seconds=10.0, max_batch=8, vocab=1000)


def test_same_seed_same_requests():
    a, b = _plan(BIG_SEED), _plan(BIG_SEED)
    assert [(p.due, p.max_new, p.fill) for p in a] == [(p.due, p.max_new, p.fill) for p in b]
    assert all(np.array_equal(p.prompt, q.prompt) for p, q in zip(a, b))


def test_seeds_reorder_one_multiset_of_sizes_and_gaps():
    a, b = _plan(1), _plan(BIG_SEED)
    arr_a = [p for p in a if not p.fill]
    arr_b = [p for p in b if not p.fill]
    assert sorted(len(p.prompt) for p in arr_a) == sorted(len(p.prompt) for p in arr_b)
    assert sorted(p.max_new for p in arr_a) == sorted(p.max_new for p in arr_b)
    assert [len(p.prompt) for p in arr_a] != [len(p.prompt) for p in arr_b]
    win_a = [p.due for p in arr_a if p.due >= MIX["warm"]["seconds"]]
    win_b = [p.due for p in arr_b if p.due >= MIX["warm"]["seconds"]]
    assert len(win_a) == len(win_b) and win_a[0] == win_b[0] == MIX["warm"]["seconds"]
    assert win_a != win_b
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_warm_up_and_window_hold_rate_times_duration_arrivals():
    arr = [p for p in _plan(3, dict(MIX, arrival={"process": "poisson", "rate": 20.0}))
           if not p.fill]
    dues = np.array([p.due for p in arr])
    assert np.all(np.diff(dues) >= 0) and dues[0] == 0.0
    assert np.sum(dues < 2.0) == 40 and np.sum((dues >= 2.0) & (dues < 12.0)) == 200
    assert dues[-1] < 12.0


def test_residual_fill_is_length_biased_and_within_each_total():
    rng = np.random.default_rng(0)
    lengths = traffic.lognormal_lengths(MIX["output"], 4096, rng)
    total, left = traffic.residual_fill(lengths, 20000, rng)
    assert np.all((left >= 1) & (left <= total))
    # length-biased mean is E[L^2]/E[L]; the residual's is about half of it
    biased = (lengths.astype(float) ** 2).mean() / lengths.mean()
    assert abs(total.mean() / biased - 1.0) < 0.05
    assert abs(left.mean() / ((biased + 1) / 2) - 1.0) < 0.05


def test_fill_requests_carry_the_context_already_generated():
    plan = _plan(5)
    fill = [p for p in plan if p.fill]
    assert len(fill) == 8 and all(p.due == 0.0 for p in fill)
    assert max(len(p.prompt) for p in fill) <= MIX["prompt"]["max"] + MIX["output"]["max"]
    assert traffic.fill_count({"warm": {"fill": 3}}, 32) == 3


def test_every_mix_on_disk_fits_its_configurations_capacity():
    bench = run.benchmark()
    for w in bench["workloads"]:
        mix, cap = run.traffic_file(w["traffic"]), run.config_file(w["config"])
        assert mix["prompt"]["max"] + mix["output"]["max"] <= cap["serving"]["capacity"]
        assert mix["arrival"]["rate"] > 0 and mix["users"]


# (fill, arrivals) of one run at run_seconds, as PERF.md section 4 gives them:
# chat 20 + 0.85 x (10 + 51), code 14 + 1.3 x (15 + 51), each part rounded
PLANNED = {"chat": (20, 8 + 43), "code": (14, 20 + 66)}


@pytest.mark.parametrize("mix", ["chat", "code"])
def test_a_cells_mix_plans_rate_times_warm_up_and_window(mix):
    """Each run of the mix offers the fill, rate x warm-up seconds and rate x
    run_seconds arrivals, every length inside the mix's bounds."""
    t = run.traffic_file(mix)
    seconds = run.benchmark()["run_seconds"]
    rate, warm = t["arrival"]["rate"], t["warm"]["seconds"]
    planned = traffic.plan(t, seed=BIG_SEED, seconds=seconds, max_batch=32, vocab=151936)
    fill = [p for p in planned if p.fill]
    arr = [p for p in planned if not p.fill]
    assert len(fill) == traffic.fill_count(t, 32) and all(p.due == 0.0 for p in fill)
    assert sum(p.due < warm for p in arr) == round(rate * warm)
    assert sum(p.due >= warm for p in arr) == round(rate * seconds)
    assert max(p.due for p in arr) < warm + seconds
    lo, hi = t["prompt"]["min"], t["prompt"]["max"]
    assert all(lo <= len(p.prompt) <= hi for p in arr)
    assert all(t["output"]["min"] <= p.max_new <= t["output"]["max"] for p in arr)
    assert all(lo <= len(p.prompt) <= hi + t["output"]["max"] for p in fill)
    assert (len(fill), len(arr)) == PLANNED[mix]
