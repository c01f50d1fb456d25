"""BENCHMARK.json against the contract it was written to, and every file
it names: a refusal before any run costs a PR, so the rules that can be
checked here are."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

from vbench import manifest, traffic  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MAN = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state_size|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per_tok)")
METRICS = [(g, m) for g in ("end_to_end", "per_layer") for m in MAN[g]]


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(MAN["paths"]) <= 16 and 1 <= len(MAN["command"]) <= 32
    assert all(_line(w) for w in MAN["command"])
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(REPO, p))


def test_a_full_check_of_24_cells_fits_the_driver_day():
    runs = 2 + 14 * 24
    total = runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_command_names_nothing_outside_paths():
    for word in MAN["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word == p or word.startswith(p + "/")
                       for p in MAN["paths"])
    mod = MAN["command"][MAN["command"].index("-m") + 1]
    assert os.path.exists(os.path.join(REPO, *mod.split(".")) + ".py")
    assert mod.split(".")[0] in MAN["paths"]


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_configuration_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and _line(cfg["source"]) and _line(cfg["why"])
    assert any(cfg["file"].startswith(p + "/") for p in MAN["paths"])
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    with open(os.path.join(REPO, cfg["file"])) as f:
        body = json.load(f)
    assert body["source"] == cfg["source"]
    assert sorted(body["reduced"]) == sorted(cfg["reduced"])
    assert any(w["config"] == cfg["name"] for w in MAN["workloads"])
    files = [c["file"] for c in MAN["configs"]]
    assert files.count(cfg["file"]) == 1
    for mod in ("reference", "sut"):
        assert os.path.exists(os.path.join(
            REPO, "vbench", mod, f"{body['family']}.py"))
    lim = body["check"]["limits"]
    assert lim and set(lim) <= {"logit_gap_max", "logit_gap_mean"}


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    assert cell["config"] in [c["name"] for c in MAN["configs"]]
    mix = traffic.load_mix(cell["traffic"], REPO)
    cfg = manifest.config(MAN, REPO, cell["config"])
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    assert longest <= cfg["max_position_embeddings"]
    assert mix["output"]["max"] <= cfg["serving"]["max_new_tokens"]
    reports = [m["name"] for m in manifest.metrics_of(
        MAN, "end_to_end", cell["name"])]
    assert "setup_s" in reports and len(reports) >= 2
    assert manifest.metrics_of(MAN, "per_layer", cell["name"])


def test_cells_are_distinct_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    names = [w["name"] for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(set(names)) == len(names)
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(names) // 4)
    assert 1 <= len(names) <= 24 and 1 <= len(MAN["configs"]) <= 24


@pytest.mark.parametrize("group,m", METRICS,
                         ids=[f"{g}.{m['name']}" for g, m in METRICS])
def test_metric_entry(group, m):
    base = {"name", "unit", "better", "source"}
    base |= {"bound"} if group == "end_to_end" else {"layer", "moves"}
    assert base <= set(m) <= base | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = [w["name"] for w in MAN["workloads"]]
    assert set(m.get("workloads", cells)) <= set(cells)
    assert os.path.exists(os.path.join(
        REPO, "vbench", "metrics", f"{m['name']}.py"))
    if group == "end_to_end":
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert _line(m["layer"])
        moved = [e for e in MAN["end_to_end"] if e["name"] == m["moves"]]
        assert len(moved) == 1
        reported_in = set(moved[0].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reported_in
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_metric_names_are_unique_and_setup_is_bounded():
    names = [m["name"] for _, m in METRICS]
    assert len(set(names)) == len(names)
    setup = [m for m in MAN["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and setup[0]["bound"] <= 0.1
    assert 1 <= len(MAN["end_to_end"]) <= 16
    assert 1 <= len(MAN["per_layer"]) <= 128


def test_layers_are_the_ones_perf_md_lists():
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    for m in MAN["per_layer"]:
        assert m["layer"] in perf, m["layer"]


def test_files_under_paths_are_named_from_name_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MAN["paths"]:
        for d, dirs, files in os.walk(os.path.join(REPO, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert ok.match(rel), rel


def test_traffic_files_are_data():
    for w in MAN["workloads"]:
        path = os.path.join(REPO, "vbench", "traffic", f"{w['traffic']}.json")
        assert os.path.exists(path)
    for f in os.listdir(os.path.join(REPO, "vbench", "traffic")):
        assert f.endswith((".json", ".jsonl", ".toml", ".txt", ".csv"))
