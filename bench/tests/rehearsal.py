"""Cells at a size the CPU test run can hold, driven through the harness
(everything a run does but the look for a chip).

Rehearsed: every cell of ``BENCHMARK.json``, and every mix under
``bench/traffic/`` that no cell runs yet, on the first configuration by
name (at this size the configurations differ only in their backend)."""
from __future__ import annotations

import time

from bench import cells, harness, roofline

SCALE = 64          # every count of the mix over this; 4096 keys, 8192 slots


def _stems(sub: str) -> list[str]:
    return sorted(p.name[: -len(".json")]
                  for p in (cells.BENCH / sub).glob("*.json"))


def planned() -> dict:
    """Cell name -> (configuration, mix, chips) of every cell rehearsed."""
    out = {w["name"]: (w["config"], w["traffic"], int(w["chips"]))
           for w in cells.spec()["workloads"]}
    used = {t for _, t, _ in out.values()}
    first = _stems("configs")[0]
    for t in _stems("traffic"):
        if t not in used:
            out.setdefault(t, (first, t, 1))
    return out


def workloads() -> list[str]:
    return list(planned())


def tiny(name: str) -> cells.Cell:
    config, traffic, _ = planned()[name]
    mix = {k: max(1, v // SCALE) if type(v) is int else v
           for k, v in cells.traffic(traffic).items()}
    mix["warmup_batches"] = 4
    cfg = dict(cells.config(config), keys=4096, chunk=256)
    e2e = cells.reports(cells.spec()["end_to_end"], name)
    return cells.Cell(name=name, config=cfg, traffic=mix, chips=1,
                      per_layer=(), end_to_end=e2e)


def traffic(name: str, seed: int = 1):
    return harness.traffic(tiny(name), seed)


def run(name: str, seed: int = 2**31 + 3, seconds: float = 0.4, wrap=None,
        monkeypatch=None):
    import jax
    dev = jax.devices()[:1]
    monkeypatch.setitem(roofline.PEAKS, dev[0].device_kind,
                        roofline.PEAKS["TPU v5 lite"])
    return harness.run(tiny(name), seed, seconds, False,
                       t_start=time.perf_counter(), devices=dev,
                       compiles=harness.Compiles(), wrap=wrap)
