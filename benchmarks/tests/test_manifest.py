"""Lint of BENCHMARK.json against the builder's contract and against the
files it names."""

import os
import re

import pytest

from benchmarks import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def m():
    return manifest.load()


def test_top_level(m):
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"]
    assert m["command"][1].startswith("benchmarks/")
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(m):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in m[group]]
        assert len(names) == len(set(names))
        for x in m[group]:
            assert NAME.match(x["name"]), x["name"]
            for key in ("why", "layer", "source"):
                if key in x and group in ("configs", "workloads", "per_layer"):
                    assert 1 <= len(x[key]) <= 200 and "\n" not in x[key] and "\t" not in x[key]
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]), x["unit"]
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.25
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"]) and NAME.match(w["config"])
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert "setup_s" in [x["name"] for x in m["end_to_end"]]
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 2)


def test_every_file_a_cell_names_exists_and_every_config_is_used(m):
    used = set()
    for w in m["workloads"]:
        cell = manifest.Cell(m, w["name"])
        used.add(cell.config_name)
        assert cell.config["validators"] > 0
        assert hasattr(cell.entry, "call") and hasattr(cell.loop, "run")
        assert cell.config.get("reduced") == next(
            c["reduced"] for c in m["configs"] if c["name"] == cell.config_name)
    assert used == {c["name"] for c in m["configs"]}
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith("benchmarks/") and os.path.exists(os.path.join(manifest.ROOT, f))


def test_readers_say_what_the_manifest_says(m):
    for x in m["end_to_end"]:
        r = manifest.reader("end_to_end", x["name"])
        assert (r.NAME, r.UNIT, r.BETTER, r.SOURCE) == (
            x["name"], x["unit"], x["better"], x["source"])
    layers = set()
    for x in m["per_layer"]:
        r = manifest.reader("layers", x["name"])
        assert (r.NAME, r.UNIT, r.BETTER, r.SOURCE, r.LAYER, r.MOVES) == (
            x["name"], x["unit"], x["better"], x["source"], x["layer"], x["moves"])
        layers.add(x["layer"])
    perf = open(os.path.join(manifest.ROOT, "PERF.md")).read()
    for layer in layers:
        assert f"**{layer}**" in perf, f"PERF.md section 3 does not list the layer {layer!r}"


def test_every_moves_is_reported_by_each_reporting_cell(m):
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = [w["name"] for w in m["workloads"]]
    for x in m["per_layer"]:
        assert x["moves"] in e2e and x["moves"] != "setup_s"
        moved = e2e[x["moves"]].get("workloads", cells)
        for cell in x.get("workloads", moved):
            assert cell in cells and cell in moved
    for cell in cells:
        c = manifest.Cell(m, cell)
        names = [x["name"] for x in c.end_to_end()]
        assert "setup_s" in names and len(names) >= 2 and c.per_layer()
    for x in m["per_layer"]:
        if x["name"].endswith("_roofline") or "mfu" in x["name"]:
            assert x["unit"] == "%"
