"""What is particular to a block lives in its architecture family,
bench/arch/<arch>.py, named by the configuration file: a second family is
taken from new files alone, and Qwen3's costs are those the harness had
before they moved there."""

import json
from types import SimpleNamespace

import pytest

from bench import costs, run

TOY = '''
import numpy as np

KEYS = {"hidden_size": "d_model", "num_hidden_layers": "num_layers",
        "n_routed_experts": "num_experts", "kv_lora_rank": "kv_lora_rank"}
GEMMS = ("mla.q", "mla.dkv", "mla.o", "moe.gate", "moe.up", "moe.down", "moe.shared")
ATTENTION = "mla.paged"
CALLS = []


def logit_gaps(params, config, prompt, served, capacity):
    experts = params["groups"][0]["k1"]["ffn"]["experts"]["w_gate"].shape[1]
    CALLS.append((list(prompt), list(served), capacity, experts))
    return np.zeros(len(served))
'''

TOY_CONFIG = {
    "name": "toy", "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite",
    "registry": "deepseek-v2-lite-16b_smoke", "arch": "toy",
    "hidden_size": 64, "num_hidden_layers": 2, "n_routed_experts": 2, "kv_lora_rank": 32,
    "reduced": {"num_hidden_layers": {"published": 3, "here": 2},
                "n_routed_experts": {"published": 4, "here": 2}},
    "quant_policy": "*=bf16",
    "kv": {"dtype": "bfloat16", "layout": "paged", "block_size": 16},
    "serving": {"max_batch": 2, "prefill_chunk": 16, "capacity": 64, "num_pages": 10},
}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    for kind in ("arch", "configs"):
        (tmp_path / kind).mkdir()
    (tmp_path / "arch" / "toy.py").write_text(TOY)
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    monkeypatch.setattr(run, "BENCH", tmp_path)
    return run.config_file("toy")


def test_a_second_family_needs_no_edit(toy):
    assert run.available("arch") == ["toy"] and run.available("configs") == ["toy"]
    cfg = run.model_config(toy)
    assert (cfg.num_layers, cfg.num_experts, cfg.attn_type) == (2, 2, "mla")

    fam = run.family(toy)
    qwen3_names = {n: {"pallas": 1} for n in ("attn.q", "mlp.up", "attn.paged")}
    faults = run.kernel_faults({"kernels": {"paths": qwen3_names, "fallbacks": {}}},
                               "pallas", fam)
    assert faults == [f"{n} never traced" for n in (*fam.GEMMS, fam.ATTENTION)]
    own = {n: {"pallas": 2} for n in (*fam.GEMMS, fam.ATTENTION)}
    assert run.kernel_faults({"kernels": {"paths": own, "fallbacks": {}}}, "pallas", fam) == []

    sample = [SimpleNamespace(req=SimpleNamespace(prompt=[1, 2, 3], out=[4, 5, 6])),
              SimpleNamespace(req=SimpleNamespace(prompt=[7], out=[8]))]
    got = run.check(toy, 11, sample)
    assert got == {"max_logit_gap": 0.0, "served_tokens": 4, "tokens_at_reference_best": 4,
                   "requests": 2}
    assert fam.CALLS == [([1, 2, 3], [4, 5, 6], 64, 2), ([7], [8], 64, 2)]


def _cut(**edits):
    config = dict(run.config_file("qwen3-8b-l12"))
    for key, value in edits.items():
        if key == "reduced":
            config["reduced"] = {**config["reduced"], **value}
        else:
            config[key] = value
    return config


@pytest.mark.parametrize("config, error", [
    (_cut(reduced={"max_position_embeddings": {"published": 40960, "here": 8192}}),
     "not in the qwen3 family's KEYS"),
    (_cut(reduced={"num_hidden_layers": {"published": 36, "here": 6}}),
     "is 6 here but the file holds 12"),
    (_cut(hidden_size=2048), "registry's d_model is 4096"),
], ids=["key_not_in_KEYS", "here_not_the_files_value", "width_not_the_registrys"])
def test_a_cut_the_family_cannot_apply_raises(config, error):
    with pytest.raises(ValueError, match=error):
        run.model_config(config)


def test_a_configuration_without_arch_raises():
    config = dict(run.config_file("qwen3-0.6b"))
    del config["arch"]
    with pytest.raises(ValueError, match="names no architecture family"):
        run.family(config)
    with pytest.raises(ValueError, match="names no architecture family"):
        run.model_config(config)


# costs.attn_cost(config, rows, 128), attn_cost(config, rows, 16, 1),
# step_useful_time at the quant policy and at *=bf16 on a TPU v5e, as the
# harness computed them before the move (pinned, exact)
ROWS = [[(127, 1)], [(0, 64)], [(255, 1), (1000, 1), (4095, 1)],
        [(300, 64), (1023, 1), (8190, 1), (0, 17)]]
PINNED = {
    "qwen3-8b-l12": [
        ((2097152.0, 540672.0), (2097152.0, 278528.0), 1.822828720889681e-05, 2.9950950010152284e-05),
        ((34078720.0, 1572864.0), (34078720.0, 1179648.0), 0.000762472158262797, 0.0015127225775431472),
        ((87703552.0, 22069248.0), (87703552.0, 11026432.0), 5.9643974073390935e-05, 9.481196247715736e-05),
        ((502136832.0, 41205760.0), (502136832.0, 21053440.0), 0.0010338044949069632, 0.0020067855074111674),
    ],
    "qwen3-0.6b": [
        ((1048576.0, 532480.0), (1048576.0, 270336.0), 3.969784728794513e-06, 6.199639065989847e-06),
        ((17039360.0, 1048576.0), (17039360.0, 655360.0), 0.00014744014865300113, 0.00029015082623350255),
        ((43851776.0, 22044672.0), (43851776.0, 11001856.0), 1.7694985374200797e-05, 2.4384548385786802e-05),
        ((251068416.0, 40525824.0), (251068416.0, 20373504.0), 0.00022802510564730498, 0.0004131030156345177),
    ],
}


@pytest.mark.parametrize("name, i", [(n, i) for n in PINNED for i in range(len(ROWS))])
def test_qwen3_costs_are_the_harness_s_before_the_move(name, i):
    config, rows, pk = run.config_file(name), ROWS[i], costs.peaks("TPU v5 lite")
    want = PINNED[name][i]
    assert costs.attn_cost(config, rows, 128) == want[0]
    assert costs.attn_cost(config, rows, 16, 1) == want[1]
    assert costs.step_useful_time(config, config["quant_policy"], rows, pk) == want[2]
    assert costs.step_useful_time(config, "*=bf16", rows, pk) == want[3]
    fam = run.family(config)
    assert fam.attn_cost(config, rows, 128) == want[0]
    assert fam.step_useful_time(config, "*=bf16", rows, pk) == want[3]
