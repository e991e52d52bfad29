"""The op-path decoder and the reduction by the program's own names: device
seconds per named scope, host seconds per engine span, idle gaps by the
innermost span."""
from __future__ import annotations

import json
import pathlib
import struct
import types

import pytest

from bench import scopes, trace, xplane

DATA = pathlib.Path(__file__).parent / "data"


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(num: int, body: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(body)) + body


def _int(num: int, v: int) -> bytes:
    return _varint(num << 3) + _varint(v % (1 << 64))


def _stat(mid: int, num: int, v) -> bytes:
    val = _len(num, v.encode()) if isinstance(v, str) else _int(num, v)
    return _int(1, mid) + val


def _event_meta(eid: int, name: str, stats) -> bytes:
    body = _int(1, eid) + _len(2, name.encode())
    return body + b"".join(_len(5, _stat(*s)) for s in stats)


def _plane(name: str, events, stat_names) -> bytes:
    body = _int(1, 9) + _len(2, name.encode())
    body += _len(3, _len(2, b"XLA Ops"))          # a line: skipped
    for eid, ev in events:
        body += _len(4, _int(1, eid) + _len(2, ev))
    for sid, sname in stat_names.items():
        body += _len(5, _int(1, sid) + _len(2, _int(1, sid)
                                            + _len(2, sname.encode())))
    body += _varint(7 << 3 | 1) + struct.pack("<d", 1.5)   # unknown field
    return _len(1, body)


def test_decoder_reads_an_xspace():
    names = {1: "tf_op", 2: "program_id", 3: "source", 4: "path"}
    events = [
        (5, _event_meta(5, "%fusion.1 = s32[4] fusion()",
                        [(1, 5, "jit(f)/dhash.lookup/eq:"), (2, 4, 77)])),
        # a path interned as a stat name (ref_value)
        (6, _event_meta(6, "%copy.2 = s32[4] copy()",
                        [(1, 7, 4), (3, 5, "x.py:1")])),
        (7, _event_meta(7, "%cond.3 = s32[4] conditional()", [])),
    ]
    data = (_plane("/host:CPU", events, names)
            + _plane("/device:TPU:0", events, names) + _len(2, b"an error"))
    got = xplane.device_ops(data)
    assert list(got) == ["/device:TPU:0"]
    assert got["/device:TPU:0"] == [
        xplane.OpMeta("%fusion.1 = s32[4] fusion()",
                      "jit(f)/dhash.lookup/eq:", 77),
        xplane.OpMeta("%copy.2 = s32[4] copy()", "path", None),
        xplane.OpMeta("%cond.3 = s32[4] conditional()", "", None)]


def test_decoder_reads_the_chip_trace():
    """The ops of ``ycsb_c.zipf``'s ``cond.7`` lie in ``dhash.lookup``'s
    steady branch (``branch_0`` is the false branch)."""
    metas = xplane.read(DATA / "ycsb_small.xplane.pb")["/device:TPU:0"]
    by = {trace.short(m.name): m for m in metas if m.name.startswith("%")}
    assert by["fusion.12"].tf_op == "jit(lookup)/cond/branch_0_fun/reduce_min:"
    assert by["fusion.12"].program_id == 13367156585635835031
    assert by["cond.7"].tf_op == ""


def test_tf_ops_keys_by_name_and_program():
    m = xplane.OpMeta
    got = xplane.tf_ops([m("a", "p/x", 1), m("a", "q/x", 2), m("b", "r", 1)])
    assert got[("a", 1)] == "p/x" and got[("a", 2)] == "q/x"
    assert ("a", None) not in got            # two paths: no name-only key
    assert got[("b", None)] == "r"


@pytest.mark.parametrize("tf_op,want", [
    ("jit(f)/dhash.lookup/cond/branch_1_fun/dhash.hazard/eq:",
     ("dhash.lookup", "dhash.hazard")),
    ("jit(fused)/vmap(dhash.insert)/while/body", ("dhash.insert",)),
    ("jit(f)/dhash.rebuild_step", ("dhash.rebuild_step",)),
    ("jit(f)/dhash.lookups/x", ()),
    ("", ()),
])
def test_scopes_of(tf_op, want):
    assert scopes.scopes_of(tf_op) == want


def _profile(modules, ops, host):
    def ev(name, s, e, **stats):
        return types.SimpleNamespace(name=name, start_ns=s, duration_ns=e - s,
                                     stats=stats)

    def line(name, evs):
        return types.SimpleNamespace(name=name, events=[ev(*x) for x in evs])

    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU",
                              lines=[line("python", host)]),
        types.SimpleNamespace(name="/device:TPU:0",
                              lines=[line("XLA Modules", modules),
                                     line("XLA Ops", ops)])])


def test_reduce_synthetic():
    m = xplane.OpMeta
    metas = {"/device:TPU:0": [
        m("%l1", "jit(s)/dhash.lookup/gather:", 1),
        m("%hz", "jit(s)/dhash.lookup/cond/dhash.hazard/eq:", 1),
        m("%l2", "jit(s)/dhash.lookup/reduce_min:", 1),
        m("%in", "jit(s)/dhash.insert/scatter:", 1),
        m("%rb", "jit(s)/dhash.rebuild_step/x:", 1),
        # one op name in two programs: the program breaks the tie
        m("%f", "jit(s)/dhash.finish_same_shape/select:", 1),
        m("%f", "jit(t)/dhash.delete/select:", 2),
        m("%cond", "", 1)]}
    modules = [("jit_s(1)", 100, 1100), ("jit_t(2)", 1200, 1300)]
    ops = [("%cond", 100, 900),              # its branch ops are scoped
           ("%l1", 100, 300), ("%hz", 300, 400), ("%l2", 350, 500),
           ("%in", 500, 700), ("%copy", 700, 800),
           ("%rb", 1000, 1050), ("%f", 1050, 1100), ("%f", 1200, 1250)]
    host = [("bench.submit", 50, 1150), ("bench.fetch", 1150, 1400),
            ("dhash.engine.step", 60, 1140),
            ("dhash.engine.put", 60, 90), ("dhash.engine.dispatch", 90, 200),
            ("dhash.engine.poll", 880, 1000), ("other", 0, 5000)]
    r = scopes.reduce(_profile(modules, ops, host), metas)
    ns = 1e-9
    assert r.scopes_s == pytest.approx({
        "dhash.lookup": 400 * ns,            # union of [100, 500)
        "dhash.hazard": 100 * ns,
        "dhash.insert": 200 * ns,
        "dhash.rebuild_step": 50 * ns,
        "dhash.finish_same_shape": 50 * ns,
        "dhash.delete": 50 * ns,
        "unscoped": 200 * ns,                # [700, 900): %cond, %copy
    })
    busy = trace.reduce(_profile(modules, ops, host)).busy_s
    outer = sum(r.scopes_s.get(k, 0) for k in scopes.OUTER)
    assert outer + r.scopes_s["unscoped"] == pytest.approx(busy)
    assert r.program_spans_s == pytest.approx({
        "dhash.engine.step": 1080 * ns, "dhash.engine.put": 30 * ns,
        "dhash.engine.dispatch": 110 * ns, "dhash.engine.poll": 120 * ns})
    # device 0 idle: [50, 100) in put (innermost for 30 of 50),
    # [900, 1000) in poll, [1100, 1200) in step, submit, then fetch
    # (fetch innermost for 50 of 100), [1250, 1400) in fetch
    assert r.idle_by_program_span == pytest.approx({
        "dhash.engine.put": 50 * ns, "dhash.engine.poll": 100 * ns,
        "bench.fetch": 250 * ns})
    b = scopes.breakdown(r, batches=2)
    assert b["device_scopes"]["dhash.lookup"] == pytest.approx(200 * ns * 1e3)
    assert b["idle_program_spans"][0] == ["bench.fetch", pytest.approx(2.5e-7)]


def test_reduce_synthetic_with_no_program_names():
    """On a trace of a program without scopes or engine spans every busy
    second is ``unscoped`` and idle gaps fall to the ``bench.*`` spans, as
    ``bench/trace.py`` puts them."""
    ops = [("%a", 100, 300), ("%b", 400, 500)]
    host = [("bench.submit", 50, 330), ("bench.fetch", 330, 600)]
    prof = _profile([("jit_s(1)", 100, 500)], ops, host)
    r = scopes.reduce(prof, {})
    base = trace.reduce(prof)
    assert r.scopes_s == pytest.approx({"unscoped": base.busy_s})
    assert r.program_spans_s == {}
    assert r.idle_by_program_span == pytest.approx(base.idle_by_span)


def test_reduce_rehash_chip_trace():
    """A trace recorded on a TPU v5e with the program's names
    (``paper_mix.rehash``, a 0.18 s window of four batches) reduces to
    what that run computed from it, by ``bench/trace.py`` and by the
    names."""
    from jax.profiler import ProfileData
    path = DATA / "rehash_small.xplane.pb"
    want = json.loads(path.with_suffix(".json").read_text())
    prof = ProfileData.from_file(str(path))
    base = trace.reduce(prof)
    assert base.spans == want["spans"]
    for k in ("window_s", "busy_s", "all_to_all_s", "ops_s", "idle_by_span"):
        assert getattr(base, k) == pytest.approx(want[k])
    got = scopes.reduce(prof, xplane.read(path))
    for k in ("scopes_s", "program_spans_s", "idle_by_program_span"):
        assert getattr(got, k) == pytest.approx(want[k])
    s = got.scopes_s
    outer = sum(s.get(k, 0) for k in scopes.OUTER) + s["unscoped"]
    assert outer == pytest.approx(base.busy_s, rel=1e-6)
    assert s["dhash.hazard"] <= s["dhash.lookup"] + s["dhash.delete"]
    # mid-rehash the autostart takes its identity branch: what runs in its
    # conditional is the swap's selects, which keep their own scope
    assert set(s) == set(scopes.SCOPES) - {"dhash.rebuild_autostart"} \
        | {"unscoped"}
