"""`BENCHMARK.json` against the benchmark's contract, and every file it
names, found by name."""
import json
import re

import jax
import pytest

from conftest import BENCH, ROOT

from bench.harness import Context
from bench.spec import load_cell, reports
from bench.weights import check_layout

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[k]}) == len(SPEC[k])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= 1


def test_end_to_end_bounds_and_sources():
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_what_the_contract_asks(cell):
    e2e = [m for m in SPEC["end_to_end"] if reports(m, cell)]
    layer = [m for m in SPEC["per_layer"] if reports(m, cell)]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in layer:
        # what it moves is an end-to-end metric this cell reports
        assert m["moves"] in {x["name"] for x in e2e}
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_per_layer_lists_name_existing_cells():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in SPEC["per_layer"]:
        assert "\n" not in m["layer"] and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        assert not (m["name"].endswith("_roofline") and m["unit"] != "%")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = load_cell(cell)
    assert c.driver().run and c.family().layout
    assert c.config["registry"] and "model" in c.config


@pytest.mark.parametrize("cfg", [c["name"] for c in SPEC["configs"]])
def test_config_is_the_registry_one_and_its_layout_the_programs(cfg):
    """The file's sizes are the registry's, but for the keys it lists as
    changed; the family's weight layout names every parameter of the
    program's tree with its shape and type."""
    from repro.models import build_model
    entry = next(c for c in SPEC["configs"] if c["name"] == cfg)
    cell = next(w["name"] for w in SPEC["workloads"] if w["config"] == cfg)
    ctx = Context(load_cell(cell), 0, 1.0, False, started=0.0)
    assert sorted(ctx.cell.config["reduced"]) == sorted(entry["reduced"])
    model = build_model(ctx.program_config())
    check_layout(ctx.family.layout(ctx.model),
                 jax.eval_shape(model.init, jax.random.PRNGKey(0)))
