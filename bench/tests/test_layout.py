"""``BENCHMARK.json`` and the files the harness finds by its names."""
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_experts_per_tok"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_uniqueness():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    cfg = json.loads((ROOT / conf["file"]).read_text())
    assert conf["file"].startswith("bench/")
    assert set(conf["reduced"]) == set(cfg["reduced"])
    for key in conf["reduced"]:
        assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
        assert cfg["published"][key] != cfg[key]
    assert (ROOT / "bench" / "references" /
            f"{cfg['reference']}.py").is_file()


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(cell):
    assert cell["chips"] == 1
    assert len(cell["why"]) <= 200
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()
    c = json.loads((ROOT / "bench" / "cells" /
                    f"{cell['name']}.json").read_text())
    assert c["arrival"]["rate_per_s"] > 0 and c["lead_s"] >= 0


def test_metrics_have_readers_and_known_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
