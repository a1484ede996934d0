"""The harness finds configurations, traffic mixes, per-layer metrics and
architecture families by file name, and BENCHMARK.json names only what is on
disk, consistently."""

import json

import pytest

from bench import run

BENCH = run.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_enumerates_by_file_name():
    assert {"qwen3-0.6b", "qwen3-8b-l12"} <= set(run.available("configs"))
    assert {"chat", "code", "reasoning"} <= set(run.available("traffic"))
    assert "qwen3" in run.available("arch")
    assert {m["name"] for m in BENCH["per_layer"]} <= set(run.available("metrics"))
    assert not any(n.startswith("_") for n in run.available("metrics"))


def test_a_new_file_is_found_without_an_edit(tmp_path, monkeypatch):
    for kind in ("configs", "traffic", "metrics", "arch"):
        (tmp_path / kind).mkdir()
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps({"users": "x"}))
    (tmp_path / "metrics" / "new.metric.py").write_text("def read(ctx):\n    return 42.0\n")
    monkeypatch.setattr(run, "BENCH", tmp_path)
    assert run.available("traffic") == ["burst"]
    assert run.traffic_file("burst") == {"users": "x"}
    assert run.metric_reader("new.metric").read(None) == 42.0
    (tmp_path / "arch" / "fam.py").write_text("GEMMS = ('x.q',)\n")
    assert run.available("arch") == ["fam"]
    assert run.family({"name": "c", "arch": "fam"}).GEMMS == ("x.q",)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_resolves(cell):
    w = run.workload(cell)
    config, mix = run.config_file(w["config"]), run.traffic_file(w["traffic"])
    cfg = run.model_config(config)          # widths agree with the registry
    assert cfg.num_layers == config["num_hidden_layers"]
    assert w["chips"] == 1 and config["name"] == w["config"]
    names = {m["name"] for m in run.end_to_end_for(cell)}
    assert "setup_s" in names and len(names) >= 2
    assert run.per_layer_for(cell)
    for m in run.per_layer_for(cell):
        assert m["moves"] in names, (cell, m["name"])
    assert mix["check_tokens"] > 0 and config["check"]["max_logit_gap"] is not None


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda e: e["name"])
def test_per_layer_entry_matches_its_reader(entry):
    """A metric's layer, unit, source and what it moves live in
    BENCHMARK.json alone; its reader only reads."""
    mod = run.metric_reader(entry["name"])
    assert callable(mod.read)
    assert not any(hasattr(mod, k) for k in ("LAYER", "UNIT", "SOURCE", "MOVES", "BETTER"))
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert entry["better"] in ("lower", "higher") and set(entry["workloads"]) <= set(CELLS)


def test_configs_reduced_keys_are_listed():
    for c in BENCH["configs"]:
        f = json.loads((run.ROOT / c["file"]).read_text())
        assert sorted(f["reduced"]) == sorted(c["reduced"]) and f["source"] == c["source"]


@pytest.mark.parametrize("name", run.available("configs"))
def test_each_config_names_a_family_on_disk(name):
    config = run.config_file(name)
    assert config["arch"] in run.available("arch")
    fam = run.family(config)
    assert set(config["reduced"]) <= set(fam.KEYS) and fam.GEMMS and fam.ATTENTION
    assert run.model_config(config).num_layers == config["num_hidden_layers"]


WORKLOAD_LISTS = [(kind, m["name"]) for kind in ("end_to_end", "per_layer")
                  for m in BENCH[kind] if "workloads" in m]


@pytest.mark.parametrize("kind,name", WORKLOAD_LISTS, ids=lambda x: str(x))
def test_workloads_lists_name_only_cells_that_exist(kind, name):
    (m,) = [m for m in BENCH[kind] if m["name"] == name]
    assert m["workloads"] and len(set(m["workloads"])) == len(m["workloads"])
    assert set(m["workloads"]) <= set(CELLS), (name, m["workloads"])


# each cell's end-to-end metrics, as PERF.md section 2 lists them
END_TO_END = {
    "qwen3-8b-l12.reasoning": {"itl_p95_s", "output_tok_s", "setup_s"},
    "qwen3-0.6b.chat": {"itl_p95_s", "setup_s"},
    "qwen3-8b-l12.code": {"ttft_p95_s", "itl_p95_s", "setup_s"},
}


@pytest.mark.parametrize("cell", sorted(END_TO_END))
def test_end_to_end_metrics_of_each_cell(cell):
    assert {m["name"] for m in run.end_to_end_for(cell)} == END_TO_END[cell]
    assert set(END_TO_END) == set(CELLS)
