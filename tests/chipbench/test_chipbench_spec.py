"""``BENCHMARK.json`` against the contract it is held to, and against the
files it names: every cell's configuration, traffic, driver and metric
readers are found by name, with no registry."""

from __future__ import annotations

import json
import os
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[0-9A-Za-z_][0-9A-Za-z_.-]{0,63}$")
UNIT = re.compile(r"^[0-9A-Za-z_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench", "tests/chipbench"]
    assert BENCH["command"] == ["python", "-m", "chipbench.run"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_configs_name_files_of_their_own_with_source_and_cuts():
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert c["file"].startswith("chipbench/") and c["file"] not in files
        files.add(c["file"])
        data = spec.load_json(c["file"])
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            # never a width
            assert not re.search(r"(_dim|_rank|hidden_size|n_embd|"
                                 r"intermediate|head)", key), key
            assert key in data["published"] and key in data
        for key in ("assumed", "departures", "chips", "deployment",
                    "factory", "factory_kwargs", "arch"):
            assert key in data, (c["name"], key)
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


CONFIG_FILES = sorted(f[:-5] for f in os.listdir(
    os.path.join(spec.PACKAGE_DIR, "configs")))


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_config_file_states_the_sizes_the_factory_runs(name):
    data = spec.load_json("chipbench", "configs", name + ".json")
    cfg = spec.model_config(data)
    if data["arch"] == "llama":
        assert (cfg.d_model, cfg.ffn_dim, cfg.n_heads, cfg.kv_heads,
                cfg.n_layers, cfg.vocab_size, cfg.rope_theta,
                cfg.max_seq_len, cfg.tied) == (
            data["hidden_size"], data["intermediate_size"],
            data["num_attention_heads"], data["num_key_value_heads"],
            data["num_hidden_layers"], data["vocab_size"],
            data["rope_theta"], data["max_position_embeddings"],
            data["tie_word_embeddings"])
        assert cfg.head_dim == 128
        assert cfg.param_dtype == data["torch_dtype"]
    else:
        assert (cfg.d_model, cfg.n_heads, cfg.n_layers, cfg.vocab_size,
                cfg.max_seq_len, cfg.ffn_dim) == (
            data["n_embd"], data["n_head"], data["n_layer"],
            data["vocab_size"], data["n_positions"], 4 * data["n_embd"])
    # Train-step options stay at the program's defaults.
    from ray_tpu.models import TransformerConfig

    d = TransformerConfig()
    assert (cfg.remat, cfg.scan_layers, cfg.loss_chunk, cfg.ce_impl,
            cfg.attn_impl, cfg.remat_policy) == (
        d.remat, d.scan_layers, d.loss_chunk, d.ce_impl, d.attn_impl,
        d.remat_policy)


def test_cells_find_their_traffic_driver_and_share_of_four_chip_cells():
    pairs, names = set(), set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        names.add(w["name"])
        cell = spec.load_cell(w["name"])
        assert cell["config_data"]["chips"] == w["chips"]
        driver = spec.load_part("drivers", cell["traffic_data"]["kind"])
        assert callable(driver.run)
    assert len(names) == len(BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_metrics_have_readers_units_sources_and_moves():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert callable(spec.load_part("end_to_end", m["name"]).read)
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert callable(spec.load_part("layer_metrics", m["name"]).read)
        # reported only where the metric it moves is
        where = set(m.get("workloads", cells))
        assert where <= set(e2e[m["moves"]].get("workloads", cells)), m["name"]
    every = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in every}) == len(every)
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:
        e = [m["name"] for m in spec.metrics_of(cell, "end_to_end")]
        assert "setup_s" in e and len(e) >= 2
        assert spec.metrics_of(cell, "per_layer")


def test_files_under_paths_are_named_from_name_characters():
    for base in BENCH["paths"]:
        for d, _dirs, files in os.walk(os.path.join(spec.ROOT, base)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), spec.ROOT)
                assert re.match(r"^[0-9A-Za-z_./-]+$", rel), rel


def test_traffic_files_are_data():
    for f in os.listdir(os.path.join(spec.PACKAGE_DIR, "traffic")):
        assert f.endswith(".json")
        with open(os.path.join(spec.PACKAGE_DIR, "traffic", f)) as fh:
            assert "kind" in json.load(fh)


def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    p = spec.load_peaks("TPU v5 lite")
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9)
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9 imaginary")
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_part("drivers", "no-such-kind")
