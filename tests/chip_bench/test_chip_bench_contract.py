"""BENCHMARK.json: names, units and files resolve; metrics are reported
where they say."""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys_and_paths():
    assert set(SPEC) == KEYS
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert ".." not in p.split("/") and not p.startswith("/")
        assert (ROOT / p).is_dir()
    assert len(SPEC["command"]) <= 32
    script = SPEC["command"][1]
    assert any(script.startswith(p + "/") for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_plain_and_unique(kind):
    names = [e["name"] for e in SPEC[kind]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    text_keys = ("why", "layer") + (("source",) if kind == "configs" else ())
    for e in SPEC[kind]:
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in text_keys:
            if key in e:
                assert 1 <= len(e[key]) <= 200, (e["name"], key)
                assert "\n" not in e[key] and "\t" not in e[key]


def test_every_cell_file_is_found_by_name():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
    used = set()
    for w in SPEC["workloads"]:
        assert w["config"] in configs
        assert (CHIP / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4)
        used.add(w["config"])
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    path = CHIP / "metrics" / f"{metric}.py"
    assert path.is_file()
    import sys
    sys.path.insert(0, str(CHIP))
    spec = importlib.util.spec_from_file_location(f"m_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)


def test_moves_is_reported_by_each_cell_of_the_metric():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = [w["name"] for w in SPEC["workloads"]]
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["layer"], set())
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    e2e = SPEC["end_to_end"]
    assert "setup_s" in [m["name"] for m in e2e]
    for w in SPEC["workloads"]:
        mine = [m["name"] for m in e2e
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in SPEC["per_layer"])


def test_peaks_table_names_v5e_with_its_source():
    peaks = json.loads((CHIP / "peaks.json").read_text())
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e == {"bf16_flops": 197e12, "int8_ops": 393e12,
                   "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    assert "TPU v5e" in peaks["source"]
