"""BENCHMARK.json against the files it names, and the benchmark's own rules
on names, units and which cells report which metrics."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

MAN = harness.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in MAN["workloads"]}
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def reports(cell):
    return {m["name"] for m in MAN["end_to_end"] if cell in m.get("workloads", [cell])}


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024


def test_names_and_units_use_only_allowed_characters():
    names = [c["name"] for c in MAN["configs"]] + list(CELLS) + \
        [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]]
    names += [w["config"] for w in MAN["workloads"]] + [w["traffic"] for w in MAN["workloads"]]
    names += [k for c in MAN["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    all_metrics = MAN["end_to_end"] + MAN["per_layer"]
    assert len({m["name"] for m in all_metrics}) == len(all_metrics)
    for m in all_metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_workload_file_names_known_config_traffic_runner_and_chips(cell):
    w = CELLS[cell]
    wl, cfg, tr = harness.cell_files(cell)
    assert wl["config"] == w["config"] and wl["traffic"] == w["traffic"]
    assert wl["chips"] == w["chips"] in (1, 4)
    assert wl["why"] == w["why"] and len(w["why"]) <= 200 and "\n" not in w["why"]
    assert w["config"] in {c["name"] for c in MAN["configs"]}
    assert os.path.isfile(os.path.join(harness.BENCH, "runners", wl["runner"] + ".py"))
    assert os.path.isfile(os.path.join(harness.BENCH, "refs", cfg["reference"] + ".py"))
    assert os.path.isfile(os.path.join(harness.BENCH, "controls", cfg["reference"] + ".py"))
    assert set(wl["limits"]) and all(isinstance(v, (int, float)) for v in wl["limits"].values())


@pytest.mark.parametrize("cfg", MAN["configs"], ids=lambda c: c["name"])
def test_config_files_lie_under_paths(cfg):
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    data = harness.load_json(ROOT, cfg["file"])
    assert data["name"] == cfg["name"] and data["reduced"] == cfg["reduced"]
    assert data["source"] == cfg["source"]
    assert any(w["config"] == cfg["name"] for w in MAN["workloads"])


def test_every_metric_has_a_reader():
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read), m["name"]


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_cell_reports_setup_another_end_to_end_and_a_layer_metric(cell):
    got = reports(cell)
    assert "setup_s" in got and len(got) >= 2
    assert harness.cell_metrics(MAN, cell, trace=True)


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in MAN["per_layer"]:
        assert m["moves"] in E2E
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["layer"] and "\n" not in m["layer"]
        for cell in m.get("workloads", []):
            assert cell in CELLS, (m["name"], cell)
            assert m["moves"] in reports(cell), (m["name"], cell)


def test_a_kernel_roofline_is_a_percentage():
    for m in MAN["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
