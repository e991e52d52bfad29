"""Each cell's run at a tiny size on the CPU: correct as the program is,
and not correct with the control (key fingerprints) in its place."""
from __future__ import annotations

from functools import partial

import pytest

from bench import control
from bench.tests import rehearsal

CELLS = rehearsal.workloads()


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct(name, monkeypatch):
    out = rehearsal.run(name, monkeypatch=monkeypatch)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    cell = rehearsal.tiny(name)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name, monkeypatch):
    # 8-bit fingerprints: at 4096 keys 16 bits would rarely collide
    out = rehearsal.run(name, monkeypatch=monkeypatch,
                        wrap=partial(control.Fingerprint, bits=8))
    assert not out["correct"]
    assert out["checks"]["found_wrong"]["value"] \
        + out["checks"]["value_wrong"]["value"] > 0
