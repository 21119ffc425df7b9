"""Metric arithmetic: percentiles with +inf for failures, the whole-step
token rate, the train readers on a hand-made run record, and the FLOP
functions against the program's own parameter count."""

from __future__ import annotations

import math

import pytest

from chipbench import spec, stats
from chipbench.flops import gpt2, llama


def test_percentile_is_nearest_rank():
    v = list(range(1, 11))
    assert stats.percentile(v, 90) == 9
    assert stats.percentile(v, 91) == 10
    assert stats.percentile(v, 50) == 5
    assert stats.percentile([], 90) is None
    assert stats.percentile([3.0], 90) == 3.0


def test_a_failure_counts_as_infinity_in_the_tail():
    ok = [0.1] * 9
    assert stats.percentile(ok + [stats.INF], 90) == 0.1
    assert stats.percentile(ok[:8] + [stats.INF] * 2, 90) == stats.INF
    assert stats.finite(stats.INF) == 1e12
    assert stats.finite(float("nan")) is None
    assert stats.finite(0.25) == 0.25


def test_whole_step_rate_divides_by_the_last_groups_end():
    groups = [{"t_end": 1.0, "tokens": 8192}, {"t_end": 2.5, "tokens": 8192}]
    assert stats.whole_step_rate(groups, 1) == pytest.approx(16384 / 2.5)
    assert stats.whole_step_rate(groups, 4) == pytest.approx(16384 / 2.5 / 4)
    assert stats.whole_step_rate([], 1) is None


def _train_run(group_s, steps=4, tokens_per_step=8192, chips=1):
    groups, t = [], 0.0
    for s in group_s:
        t += s
        groups.append({"t_end": t, "steps": steps,
                       "tokens": steps * tokens_per_step})
    return {"kind": "train", "cell": {"chips": chips},
            "train": {"groups": groups, "group_s": list(group_s),
                      "tokens_per_step": tokens_per_step,
                      "input_wait_s": 0.05 * t, "window_s": t}}


def test_train_readers_on_a_hand_made_run():
    run = _train_run([2.0, 2.0, 4.0, 2.0], chips=4)
    read = {n: spec.load_part(d, n).read for d, n in (
        ("end_to_end", "train_tok_s_chip"), ("layer_metrics", "train_step_ms"),
        ("layer_metrics", "input_wait_share"))}
    # 4 groups x 4 steps x 8192 tokens in 10 s over 4 chips
    assert read["train_tok_s_chip"](run) == pytest.approx(16 * 8192 / 10 / 4)
    # the median group (2 s / 4 steps), not the mean: a stall does not count
    assert read["train_step_ms"](run) == pytest.approx(500.0)
    assert read["input_wait_share"](run) == pytest.approx(5.0)
    assert read["train_step_ms"](_train_run([])) is None
    assert read["train_tok_s_chip"](_train_run([])) is None


@pytest.mark.parametrize("name,metrics,want", [
    # 16 of 64 experts held: balance is a quarter of the assignments
    ("moe_held_off_balance", {"loss": 11.0, "moe_held_share": 0.2375}, 0.0125),
    ("moe_held_off_balance", {"loss": 11.0, "moe_held_share": 0.44}, 0.19),
    ("moe_load_max", {"loss": 11.0, "moe_load_max": 4.75}, 4.75),
    # a dense step reports no such counter: nothing to read, never a 0
    ("moe_held_off_balance", {"loss": 11.0, "moe_load_max": 4.75}, None),
    ("moe_load_max", {"loss": 11.0}, None),
    # a report from before the counters reached it, or no report at all
    ("moe_held_off_balance", None, None),
    ("moe_load_max", None, None),
])
def test_counter_readers_take_the_last_steps_scalar(name, metrics, want):
    run = _train_run([2.0])
    run["cell"]["config_data"] = spec.load_json(
        "chipbench", "configs", "smallthinker-21b-a3b-ep4.json")
    if metrics is not None:
        run["train"]["step_metrics"] = metrics
    read = spec.load_part("layer_metrics", name).read
    assert read(run) == (want if want is None else pytest.approx(want))
    assert read({"kind": "train", "train": None}) is None


def test_a_held_share_where_no_expert_is_held_has_no_balance_to_be_off():
    run = _train_run([2.0])
    run["cell"]["config_data"] = spec.load_json(
        "chipbench", "configs", "olmoe-1b-7b-1chip.json")
    run["train"]["step_metrics"] = {"loss": 11.0, "moe_held_share": 1.0}
    read = spec.load_part("layer_metrics", "moe_held_off_balance").read
    assert read(run) is None


def _counter_cells():
    return [(m["name"], cell) for m in spec.load_benchmark()["per_layer"]
            if m["source"] == "program_counter" for cell in m["workloads"]]


@pytest.mark.parametrize("name,cell", _counter_cells())
def test_a_counter_reader_lists_only_cells_whose_step_reports_it(name, cell):
    """Whatever cells a later PR appends: each is a cell of the benchmark
    that trains a model with experts (the step of a dense one reports no
    routing counter), and the off-balance reader's cells hold a share."""
    cfg = spec.model_config(spec.load_cell(cell)["config_data"])
    assert cfg.n_experts > 0, (name, cell)
    if name == "moe_held_off_balance":
        assert cfg.experts_held is not None, cell
    assert callable(spec.load_part("layer_metrics", name).read)


def test_mfu_is_model_flops_over_the_peak_at_the_median_step():
    data = spec.load_json("chipbench", "configs", "gpt2-xl-1chip.json")
    run = _train_run([3.2, 3.2, 3.2], steps=8)
    run["cell"].update(config_data=data, traffic_data={"seq_len": 1024})
    mfu = spec.load_part("layer_metrics", "mfu")
    assert mfu.read(run) is None                       # no peaks: no share
    run["peaks"] = spec.load_peaks("TPU v5 lite")
    per_token = gpt2.train_flops_per_token(spec.model_config(data), 1024)
    assert mfu.read(run) == pytest.approx(
        100 * per_token * 8192 / 0.4 / 197e12)
    assert 30 < mfu.read(run) < 45


@pytest.mark.parametrize("name", ["gpt2-xl-1chip", "mistral-7b-fsdp4"])
def test_flop_functions_count_the_programs_parameters(name):
    data = spec.load_json("chipbench", "configs", name + ".json")
    cfg = spec.model_config(data)
    flops = spec.load_part("flops", data["arch"])
    assert flops.n_params(cfg) == cfg.num_params()
    assert flops.matmul_params(cfg) < cfg.num_params()


def test_train_flops_per_token_by_hand():
    from ray_tpu import models

    cfg = models.tiny(arch="llama", n_kv_heads=2)   # d 64, 4 heads of 16
    f = cfg.ffn_dim
    per_layer = 64 * 64 + 2 * 64 * 2 * 16 + 64 * 64 + 3 * 64 * f
    matmul = 2 * per_layer + 64 * 256
    attn = 2 * 2 * 2 * 4 * 16 * (128 / 2)           # layers*2*2*H*Dh*T/2
    assert llama.train_flops_per_token(cfg, 128) == 3 * (2 * matmul + attn)
    g = models.tiny()
    matmul = 2 * (4 * 64 * 64 + 2 * 64 * 256) + 64 * 256
    assert gpt2.train_flops_per_token(g, 128) == 3 * (2 * matmul + attn)


def test_six_n_is_the_bulk_of_it():
    data = spec.load_json("chipbench", "configs", "gpt2-xl-1chip.json")
    cfg = spec.model_config(data)
    per_token = gpt2.train_flops_per_token(cfg, 1024)
    assert 6 * gpt2.matmul_params(cfg) < per_token < 1.2 * 6 * cfg.num_params()
    assert math.isfinite(per_token)
