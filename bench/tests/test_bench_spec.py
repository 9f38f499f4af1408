"""BENCHMARK.json against the benchmark's contract, and the harness's
look-up by name: a cell, mix or metric is new files plus entries."""
import json
import os
import re
import shutil
import types

import pytest

from bench import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    SPEC = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank|(?<!vocab)_size|_heads?|_state|expand|headdim|"
                   r"num_experts_per_tok|d_model|d_inner)$")


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and SPEC["command"] == ["python3", "bench/run.py"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_are_files_under_paths_and_cut_no_width():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(REPO, c["file"])) as f:
            data = json.load(f)
        assert data["source"] == c["source"] and data["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in data and not WIDTH.search(key)


def test_cells_name_their_files():
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(names) == len(set(names))
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(names)
    suite = cells.Suite()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and line(w["why"])
        cell = suite.cell(w["name"])
        assert cell.traffic["name"] == w["traffic"]
        assert set(cell.limits["limits"]) == {"first_loss_gap", "loss_gap", "exchange_gap",
                                              "comm_mismatch"}
        assert cell.limits["limits"]["comm_mismatch"]["limit"] == 0
        assert cell.end_to_end and cell.per_layer


def test_metrics():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert {"setup_s", "tokens_per_s", "peak_hbm_gb"} <= e2e
    layer_names = {}
    for m in SPEC["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and line(m["layer"])
        assert m["better"] in ("lower", "higher") and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        assert os.path.exists(os.path.join(REPO, "bench", "layers", f"{m['name']}.py"))
        layer_names.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layer_names.values())
    all_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(all_names) == len(set(all_names))


def test_a_new_mix_and_metric_are_files_plus_entries(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(REPO, "bench"), bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    base = spec["workloads"][0]
    with open(bench / "traffic" / f"{base['traffic']}.json") as f:
        traffic = json.load(f)
    traffic.update(name="pame.b4x256", batch=4, seq=256)
    (bench / "traffic" / "pame.b4x256.json").write_text(json.dumps(traffic))
    (bench / "layers" / "rounds_per_chunk.py").write_text(
        '"""Rounds per chunk of the window, where the mix has chunks."""\n\n\n'
        "def read(ctx):\n"
        "    if not ctx.chunks:\n        return None\n"
        "    return ctx.rounds / ctx.chunks\n")
    shutil.copy(bench / "limits" / f"{base['name']}.json", bench / "limits" / "new.cell.json")
    spec["workloads"].append({"name": "new.cell", "config": base["config"],
                              "traffic": "pame.b4x256", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "rounds_per_chunk", "unit": "count", "better": "higher",
                              "source": "host_clock", "layer": "engine",
                              "moves": "tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    suite = cells.Suite(root=str(bench), spec_path=str(tmp_path / "BENCHMARK.json"))
    cell = suite.cell("new.cell")
    assert (cell.traffic["batch"], cell.traffic["seq"]) == (4, 256)
    assert "rounds_per_chunk" in [m["name"] for m in cell.per_layer]
    reader = suite.module("layers", "rounds_per_chunk")
    assert reader.read(types.SimpleNamespace(rounds=48, chunks=3)) == 16
    assert reader.read(types.SimpleNamespace(rounds=48, chunks=0)) is None
    with pytest.raises(KeyError):
        suite.cell("no.such.cell")
