"""The reduction from a profiler trace to busy time, idle share, op times,
collective time and idle gaps by host span."""
from __future__ import annotations

import json
import pathlib
import types

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


def _profile(device_lines, host_spans):
    def ev(name, s, e):
        return types.SimpleNamespace(name=name, start_ns=s, duration_ns=e - s)

    def line(name, evs):
        return types.SimpleNamespace(name=name,
                                     events=[ev(*x) for x in evs])

    planes = [types.SimpleNamespace(name="/host:CPU",
                                    lines=[line("python", host_spans)])]
    for i, ops in enumerate(device_lines):
        planes.append(types.SimpleNamespace(
            name=f"/device:TPU:{i}",
            lines=[line("XLA Modules", [("jit_step", 0, 10**9)]),
                   line("XLA Ops", ops)]))
    return types.SimpleNamespace(planes=planes)


def test_union_and_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert trace.clip([(0, 4), (6, 12)], 2, 10) == [(2, 4), (6, 10)]


def test_reduce_synthetic():
    host = [("bench.submit", 100, 200), ("bench.fetch", 200, 600),
            ("bench.submit", 600, 700), ("bench.fetch", 700, 1100),
            ("other.span", 0, 5000)]
    dev0 = [("fusion.1", 150, 400), ("fusion.2", 300, 500),
            ("%all-to-all.3 = s32[8] all-to-all(s32[8] %p)", 650, 750), ("fusion.1", 1000, 1300)]
    dev1 = [("%fusion.1 = s32[4] fusion(s32[4] %all-to-all.3)", 100, 1100)]
    r = trace.reduce(_profile([dev0, dev1], host))
    assert r.window_s == pytest.approx(1000e-9)
    # device 0 busy [150, 500) + [650, 750) + [1000, 1100) = 550 ns
    assert r.busy_s == pytest.approx((550 + 1000) / 2 * 1e-9)
    assert r.devices == 2
    assert r.all_to_all_s == pytest.approx(100 / 2 * 1e-9)
    assert r.ops_s["fusion.1"] == pytest.approx((250 + 100 + 1000) / 2e9)
    # device 0 idle: [100, 150) in submit, [500, 650) mostly fetch,
    # [750, 1000) in fetch
    assert r.idle_by_span == pytest.approx(
        {"bench.submit": 50e-9, "bench.fetch": 400e-9})
    assert r.spans == {"bench.submit": 2, "bench.fetch": 2}
    b = trace.breakdown(r)
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0] == ["bench.fetch", pytest.approx(400e-9)]


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(RuntimeError, match="no device ops"):
        trace.reduce(_profile([], [("bench.submit", 0, 10)]))


def test_reduce_chip_trace():
    """A trace recorded on a TPU v5e (``ycsb_c.zipf``, a 0.25 s window)
    reduces to what the run itself computed from it."""
    from jax.profiler import ProfileData
    want = json.loads((DATA / "ycsb_small.xplane.json").read_text())
    got = trace.reduce(ProfileData.from_file(
        str(DATA / "ycsb_small.xplane.pb")))
    assert got.devices == want["devices"] == 1
    assert got.spans == want["spans"]
    for k in ("window_s", "busy_s", "all_to_all_s"):
        assert getattr(got, k) == pytest.approx(want[k])
    for k in ("ops_s", "idle_by_span"):
        assert getattr(got, k) == pytest.approx(want[k])
    assert 0 < got.busy_s <= got.window_s
