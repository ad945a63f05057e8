"""BENCHMARK.json against the benchmark's contract, and every piece a cell
names found by its name in a file of its own."""

import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

M = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
WIDTHS = re.compile(r"(_dim|_rank)$|(^|_)(hidden|intermediate)_size$|latent|"
                    r"state|proj|expan|experts_per_tok")


def line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_and_sizes():
    assert set(M) == KEYS["top"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in M[section]:
            extra = {"workloads"} if section in ("end_to_end",
                                                 "per_layer") else set()
            assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16
    assert 1 <= len(M["per_layer"]) <= 128
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51


def test_command_and_paths():
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = M["command"]
    assert len(cmd) <= 32 and all(line_ok(w) for w in cmd)
    for w in cmd[1:]:
        if "/" in w:
            assert any(w.startswith(p + "/") for p in M["paths"])


def test_names_units_and_lines():
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        sec = [e["name"] for e in M[section]]
        assert len(sec) == len(set(sec))
        names += sec
    for n in names:
        assert NAME.match(n), n
    for w in M["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
    for c in M["configs"]:
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTHS.search(k), k
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in M["per_layer"]:
        assert line_ok(m["layer"])


def test_bounds_and_sources():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_metric_and_a_layer():
    for w in M["workloads"]:
        e2e = [m["name"] for m in harness.cell_metrics(M, w["name"],
                                                       "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(M, w["name"], "per_layer")


def test_per_layer_metrics_move_what_their_cells_report():
    for m in M["per_layer"]:
        for cell in m.get("workloads", [w["name"] for w in M["workloads"]]):
            names = [x["name"] for x in harness.cell_metrics(M, cell,
                                                             "end_to_end")]
            assert m["moves"] in names, (m["name"], cell)
    layers = {m["layer"] for m in M["per_layer"]}
    assert layers <= {"kernels", "model step", "device", "scheduler"}


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_each_piece_of_a_cell_is_found_by_name(cell):
    w = harness.cell_entry(M, cell)
    cfg_entry = harness.config_entry(M, w["config"])
    path = os.path.join(ROOT, cfg_entry["file"])
    assert any(cfg_entry["file"].startswith(p + "/") for p in M["paths"])
    with open(path) as f:
        cfg = json.load(f)
    for k in cfg_entry["reduced"]:
        assert k in cfg
    for k, v in cfg.get("published", {}).items():
        assert k in cfg_entry["reduced"] and cfg[k] != v
    with open(os.path.join(ROOT, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert os.path.isfile(os.path.join(ROOT, "bench", "drivers",
                                       mix["kind"] + ".py"))
    assert harness.load_limits(cell)
    for m in harness.cell_metrics(M, cell, "per_layer"):
        assert callable(harness.load_reader(m["name"]))


def test_config_files_are_distinct_and_used():
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
    assert len({(w["config"], w["traffic"]) for w in M["workloads"]}) == \
        len(M["workloads"])


def test_run_harness_names_no_cell():
    with open(os.path.join(ROOT, "bench", "run.py")) as f:
        text = f.read()
    for w in M["workloads"]:
        assert w["name"] not in text
