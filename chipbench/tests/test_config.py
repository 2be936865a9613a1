"""The configuration file states the program it expects: ``harness.sizes``
hands the reference every key of the file, and ``harness.model_config``
applies the file's ``structure`` to the registry entry, nested fields
(a chip's share of the experts) included."""
import dataclasses
import time

import pytest

import harness
from repro.configs import registry


def test_rwkv6_sizes_and_model_are_as_they_were():
    model = harness.load_json(harness.BENCH / "configs" / "rwkv6-3b.json")
    sz = harness.sizes(model)
    assert {k: sz[k] for k in (
        "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab",
        "n_layers", "norm", "eps", "gated", "causal", "tied",
        "rope_theta")} == {
        "d_model": 2560, "n_heads": 40, "n_kv_heads": 40, "head_dim": 64,
        "d_ff": 8960, "vocab": 65536, "n_layers": 4, "norm": "layernorm",
        "eps": 1e-05, "gated": False, "causal": True, "tied": False,
        "rope_theta": 10000.0}
    assert sz["head_size_divisor"] == 8          # the file's own keys too
    # the model as the harness built it before the file's structure was
    # applied: the registry entry with the file's numbers
    was = dataclasses.replace(
        registry.get_config("rwkv6-3b"), n_layers=4, d_model=2560,
        n_heads=40, n_kv_heads=40, head_dim=0, d_ff=8960, vocab_size=65536,
        norm_eps=1e-05, rope_theta=10000.0, dtype="bfloat16")
    assert harness.model_config(model, sz) == was


def test_structure_states_the_experts_a_chip_holds(data_cell):
    cell = data_cell("qwen2-moe-a2.7b.small")
    sz = harness.sizes(cell["model"])
    cfg = harness.model_config(cell["model"], sz)
    assert cfg.moe == dataclasses.replace(
        registry.get_config("qwen2-moe-a2.7b").moe, n_experts=4, top_k=2,
        expert_d_ff=48, n_shared_experts=1, shared_d_ff=96,
        per_expert_factors=True)
    assert sz["moe_intermediate_size"] == 48 and sz["num_experts"] == 4


@pytest.mark.parametrize("config", ["qwen2-moe-a2.7b.small",
                                    "jamba-v0.1-52b.small"])
def test_expert_and_pattern_files_build_a_run_and_step(data_cell, config):
    run = harness.Run(data_cell(config), 2 ** 31 + 5)
    assert run.cfg.n_layers == run.sz["n_layers"]
    assert len(run.cfg.pattern) == {"qwen2-moe-a2.7b.small": 1,
                                    "jamba-v0.1-52b.small": 8}[config]
    opt, mcfg = run.optimizer()
    drv, prog, _, _ = harness.start(run, opt, mcfg, run.runner(opt),
                                    time.perf_counter(), log=lambda m: None)
    assert drv.failed == 0 and drv.steps == 2
    assert prog["factor"] and max(prog["update"].values()) > 0


def test_heads_times_head_dim_need_not_be_the_width(data_cell):
    import jax
    from repro.models import model as model_lib
    cell = data_cell("qwen2-moe-a2.7b.small")
    cell["model"]["head_dim"] = 32               # 4 heads of 32 on 64
    sz = harness.sizes(cell["model"])
    cfg = harness.model_config(cell["model"], sz)
    assert (sz["n_heads"], sz["head_dim"]) == (4, 32)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (4, 4, 32)
    shapes = jax.eval_shape(lambda k: model_lib.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert shapes["blocks"][0]["mixer"]["q"]["w"].shape == (2, 64, 128)


@pytest.mark.parametrize("structure", [{"no_such_field": 1},
                                       {"moe": {"no_such_field": 1}},
                                       {"mamba": {"d_state": 8}}])
def test_a_field_the_model_has_not_is_refused(data_cell, structure):
    cell = data_cell("qwen2-moe-a2.7b.small")
    cell["model"]["structure"] = {**cell["model"]["structure"], **structure}
    with pytest.raises(SystemExit):
        harness.model_config(cell["model"], harness.sizes(cell["model"]))


def test_a_depth_of_part_periods_is_refused(data_cell):
    cell = data_cell("jamba-v0.1-52b.small")
    cell["model"]["num_hidden_layers"] = 12
    with pytest.raises(SystemExit, match="whole periods"):
        harness.model_config(cell["model"], harness.sizes(cell["model"]))
