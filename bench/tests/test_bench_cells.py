"""Every configuration, mix, generator, distribution, service and metric
reader loads by name, and ``BENCHMARK.json`` keeps the form the harness
relies on."""
from __future__ import annotations

import json
import re

import pytest

from bench import cells, harness, table

SPEC = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}$")


def _stems(sub, suffix):
    return sorted(p.name[: -len(suffix)]
                  for p in (cells.BENCH / sub).glob(f"*{suffix}"))


@pytest.mark.parametrize("name", _stems("configs", ".json"))
def test_config_loads(name):
    cfg = cells.config(name)
    assert cfg["name"] == name
    assert {"source", "backend", "keys", "chunk", "guarantees", "reduced",
            "assumed"} <= set(cfg)
    assert cfg["keys"] % min(table.FILL_BATCH, cfg["keys"]) == 0


@pytest.mark.parametrize("name", _stems("traffic", ".json"))
def test_mix_loads(name):
    mix = cells.traffic(name)
    assert {"why", "generator", "service", "warmup_batches",
            "checked_batches"} <= set(mix)
    assert callable(cells.module("services", mix["service"]).build)
    cell = cells.Cell(name, {"keys": 4096}, mix, 1, (), ())
    t = harness.traffic(cell, 2**31 + 1)
    b = t.batch(0)
    assert b.look.size == t.ops.lookups > 0
    assert (b.ins.size, b.dels.size) == (t.ops.inserts, t.ops.deletes)
    assert t.populate_range()[1] - t.populate_range()[0] == 4096


@pytest.mark.parametrize("kind, name, attr", [
    (kind, n, attr)
    for kind, attr in (("generators", "Traffic"), ("distributions", "draw"),
                       ("services", "build"))
    for n in _stems(kind, ".py")])
def test_module_loads(kind, name, attr):
    assert callable(getattr(cells.module(kind, name), attr))


@pytest.mark.parametrize("name", _stems("metrics", ".py"))
def test_metric_reader_loads(name):
    assert callable(harness.reader(name))


def test_every_metric_has_a_reader_and_every_cell_loads():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert set(m["name"] for m in metrics) <= set(_stems("metrics", ".py"))
    for w in SPEC["workloads"]:
        cell = cells.load(w["name"])
        assert cell.chips == w["chips"]
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_benchmark_json_form():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in SPEC["configs"]}
    assert configs == {w["config"] for w in SPEC["workloads"]}
    cell_names = {w["name"] for w in SPEC["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    named = [c["name"] for c in SPEC["configs"]] + list(cell_names) + [
        m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(named)) == len(named)
    for n in named + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for c in SPEC["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert set(c["reduced"]) <= set(cells.config(c["name"]))
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert set(m["workloads"]) <= cell_names
        assert layers.setdefault(m["name"], m["layer"]) == m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert len(json.dumps(SPEC)) < 64 * 1024
