"""``BENCHMARK.json`` and the files it names against the contract's rules."""

import json
import os
import re

import pytest

from benchmark.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) \
        and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") \
            and ".." not in word
    for path in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", path)
        assert os.path.isdir(os.path.join(ROOT, path))


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_entries_have_just_the_keys_shown():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["reduced"]) <= 16
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for w in BENCH["workloads"]:
        mine = [m["name"] for m in BENCH["end_to_end"]
                if reports(m, w["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(reports(m, w["name"]) for m in BENCH["per_layer"])


def test_every_moves_target_is_reported_where_the_metric_is():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for w in BENCH["workloads"]:
            if reports(m, w["name"]):
                assert reports(e2e[m["moves"]], w["name"]), \
                    (m["name"], w["name"])
        for cell in m.get("workloads", ()):
            assert cell in {w["name"] for w in BENCH["workloads"]}


#: what ``reduced`` may never name, at the top or inside a group: a
#: hidden, intermediate, latent, state or projection size, a key that
#: ends in ``_dim`` or ``_rank``, a head size, an expansion factor, the
#: number of experts a token takes
WIDTH = re.compile(
    r"(hidden_size|hidden_dim|d_model|n_embd|intermediate|n_inner|latent"
    r"|state_size|d_state|proj|head_dim|head_size|expand|expansion|_dim$"
    r"|_rank$|per_tok|experts_per)")


def may_be_reduced(key: str) -> bool:
    return bool(NAME.match(key)) and not WIDTH.search(key)


@pytest.mark.parametrize("key, allowed", [
    ("num_hidden_layers", True), ("n_layer", True), ("vocab_size", True),
    ("n_routed_experts_held", True), ("num_experts_held", True),
    ("hidden_size", False), ("n_embd", False), ("n_inner", False),
    ("moe_intermediate_size", False), ("kv_lora_rank", False),
    ("qk_nope_head_dim", False), ("head_dim", False), ("v_head_dim", False),
    ("num_experts_per_tok", False), ("num_experts_per_token", False),
    ("ssm_state_size", False), ("expand", False), ("proj_size", False),
    ("a key", False)])
def test_what_reduced_may_list(key, allowed):
    assert may_be_reduced(key) is allowed


def test_configurations_are_files_under_paths_at_published_widths():
    """``reduced`` lists cuts of depth and of a chip's share, never a
    width, and each cut stands beside its published value."""
    used = {w["config"] for w in BENCH["workloads"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = load(c["file"])
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert may_be_reduced(key), key
            assert key in cfg and key in cfg["assumed"], key
        assert bool(c["reduced"]) == ("published" in cfg)
    gpt = load("benchmark/configs/gpt2-medium.json")
    assert (gpt["n_embd"], gpt["n_layer"], gpt["n_head"], gpt["n_inner"],
            gpt["vocab_size"]) == (1024, 24, 16, 4096, 50257)


def test_the_staged_bert_configuration_is_at_published_widths():
    bert = load("benchmark/configs/bert-large-uncased.json")
    assert bert["reduced"] == []
    assert (bert["hidden_size"], bert["num_hidden_layers"],
            bert["num_attention_heads"], bert["intermediate_size"],
            bert["vocab_size"], bert["max_position_embeddings"]) == \
        (1024, 24, 16, 4096, 30522, 512)


def test_every_cell_and_metric_has_its_files():
    for w in BENCH["workloads"]:
        cell = load("benchmark/workloads", w["name"] + ".json")
        for key in ("name", "config", "traffic", "chips", "why"):
            assert cell[key] == w[key]
        assert os.path.exists(os.path.join(
            ROOT, "benchmark/drivers", cell["driver"] + ".py"))
        assert any(v is not None for v in cell["limits"].values())
    for m in BENCH["per_layer"]:
        spec = load("benchmark/metrics", m["name"] + ".json")
        for key in m:
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark/readers", spec["reader"] + ".py"))
    for name in os.listdir(os.path.join(ROOT, "benchmark")):
        assert re.fullmatch(r"[A-Za-z0-9_.\-]+", name)


def test_rooflines_and_mfu_are_named_as_the_contract_says():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert any("mfu" in re.split(r"[._\-]", n) for n in names)
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
