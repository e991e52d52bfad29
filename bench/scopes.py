"""The program's own names in a profiler trace: device seconds per DHash
operation, host seconds per engine span, and each idle gap put down to the
innermost span the host was in.

    python3 -m bench.scopes <trace dir> [batches]

prints the reduction of the one ``.xplane.pb`` under ``<trace dir>`` (per
batch where ``batches`` is given).  The window is ``bench/trace.py``'s: the
client's ``bench.*`` spans.

* ``scopes_s``: for each scope of ``SCOPES`` (``jax.named_scope`` names in
  ``core/dhash.py``), the union of the intervals of the device ops whose
  ``tf_op`` path holds it (``bench/xplane.py`` reads the paths), clipped to
  the window and averaged over devices; ``unscoped`` is busy less the union
  of every scoped op.  Scopes nest (``dhash.hazard`` lies inside
  ``dhash.lookup`` and ``dhash.delete``), so they do not add up to busy;
  the outer ones and ``unscoped`` do.
* ``program_spans_s``: total host seconds of each ``dhash.*`` span
  (``core/engine.py``'s ``TraceAnnotation``s) in the window.
* ``idle_by_program_span``: each idle gap of device 0 goes to the span that
  was innermost for most of it: a ``dhash.*`` span where the host was in
  one, else the ``bench.*`` span, else ``other``.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import re
import sys

from bench import trace, xplane

SCOPES = ("dhash.lookup", "dhash.insert", "dhash.delete", "dhash.hazard",
          "dhash.rebuild_step", "dhash.finish_same_shape",
          "dhash.rebuild_autostart")
# the scopes that never nest in one another and cover the engine's step
OUTER = ("dhash.lookup", "dhash.insert", "dhash.delete",
         "dhash.rebuild_step", "dhash.finish_same_shape",
         "dhash.rebuild_autostart")
UNSCOPED = "unscoped"
PROGRAM_PREFIX = "dhash."
MODULES_LINE = "XLA Modules"
_PROGRAM_ID = re.compile(r"\((\d+)\)$")
_SCOPE = {s: re.compile(r"(?:^|[/(])" + re.escape(s) + r"(?=$|[/):])")
          for s in SCOPES}


@dataclasses.dataclass
class Scoped:
    scopes_s: dict               # scope -> device seconds, and "unscoped"
    program_spans_s: dict        # dhash.* span -> host seconds
    idle_by_program_span: dict   # innermost span -> idle seconds (dev 0)


def scopes_of(tf_op: str) -> tuple:
    return tuple(s for s in SCOPES if _SCOPE[s].search(tf_op))


def _host_spans(profile) -> list:
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        for ev in line.events if ev.name.startswith(
                            (trace.SPAN_PREFIX, PROGRAM_PREFIX))]
    return out


def _device_ops(plane, paths: dict) -> list:
    """``(start, end, tf_op)`` of a device plane's ops; an op's program is
    the ``XLA Modules`` event that holds its start."""
    modules, ops = [], []
    for line in plane.lines:
        if line.name == MODULES_LINE:
            for ev in line.events:
                m = _PROGRAM_ID.search(ev.name)
                modules.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                int(m.group(1)) if m else None))
        elif line.name == trace.OPS_LINE:
            ops += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events]
    modules.sort()
    out, j = [], 0
    for s, e, name in sorted(ops):
        while j + 1 < len(modules) and modules[j + 1][0] <= s:
            j += 1
        pid = modules[j][2] if modules and modules[j][0] <= s else None
        tf_op = paths.get((name, pid), paths.get((name, None), ""))
        out.append((s, e, tf_op))
    return out


def _innermost(inside, g0, g1) -> str:
    """The span innermost for most of ``[g0, g1)``: at each instant the one
    among ``inside`` (the spans that overlap the gap) that started last."""
    if not inside:
        return "other"
    cuts = sorted({g0, g1, *(max(s, g0) for s, _, _ in inside),
                   *(min(e, g1) for _, e, _ in inside)})
    held = collections.Counter()
    for a, b in zip(cuts, cuts[1:]):
        run = [x for x in inside if x[0] <= a and x[1] >= b]
        if run:
            held[max(run, key=lambda x: (x[0], -x[1]))[2]] += b - a
    return held.most_common(1)[0][0] if held else "other"


def reduce(profile, metas: dict) -> Scoped:
    """Reduce a ``jax.profiler.ProfileData`` with the op metadata of the
    same file (``xplane.device_ops``)."""
    host = _host_spans(profile)
    bench = [(s, e) for s, e, n in host if n.startswith(trace.SPAN_PREFIX)]
    if not bench:
        raise RuntimeError("the trace holds no client spans")
    lo, hi = min(s for s, _ in bench), max(e for _, e in bench)

    def length(intervals):
        return sum(e - s for s, e in trace.union(trace.clip(intervals, lo,
                                                            hi)))

    t, busy0, n, named = collections.Counter(), None, 0, {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = _device_ops(plane, xplane.tf_ops(metas.get(plane.name, ())))
        if not ops:
            continue
        n += 1
        if busy0 is None:
            busy0 = trace.union(trace.clip([(s, e) for s, e, _ in ops],
                                           lo, hi))
        by_scope = collections.defaultdict(list)
        for s, e, tf_op in ops:
            if tf_op not in named:
                named[tf_op] = scopes_of(tf_op)
            for sc in named[tf_op]:
                by_scope[sc].append((s, e))
        for sc, iv in by_scope.items():
            t[sc] += length(iv)
        # busy less the union of every scoped op
        t[UNSCOPED] += length([(s, e) for s, e, _ in ops]) - length(
            [x for iv in by_scope.values() for x in iv])
    if not n:
        raise RuntimeError("the trace holds no device ops")
    scopes_s = {sc: v / n * 1e-9 for sc, v in t.items()}
    spans = collections.Counter()
    for s, e, name in host:
        if name.startswith(PROGRAM_PREFIX):
            spans[name] += max(0, min(e, hi) - max(s, lo))
    idle, host, first = collections.Counter(), sorted(host), 0
    for g0, g1 in trace.gaps(busy0, lo, hi):
        # spans nest at most a step deep: the first one still running
        # opened at most a step before the gap
        while first < len(host) and host[first][1] <= g0:
            first += 1
        inside = []
        for x in itertools.islice(host, first, None):
            if x[0] >= g1:
                break
            if x[1] > g0:
                inside.append(x)
        idle[_innermost(inside, g0, g1)] += g1 - g0
    return Scoped(scopes_s=scopes_s,
                  program_spans_s={k: v * 1e-9 for k, v in spans.items()},
                  idle_by_program_span={k: v * 1e-9 for k, v in idle.items()})


def read(trace_dir) -> Scoped:
    from jax.profiler import ProfileData
    path = trace.find(trace_dir)
    return reduce(ProfileData.from_file(str(path)), xplane.read(path))


def breakdown(r: Scoped, batches: int, top: int = 10) -> dict:
    """Device ms per batch of each scope, and the idle gaps by innermost
    span (seconds, most first)."""
    idle = sorted(r.idle_by_program_span.items(), key=lambda kv: -kv[1])
    return {"device_scopes": {k: v / batches * 1e3
                              for k, v in r.scopes_s.items()},
            "idle_program_spans": [[k, v] for k, v in idle][:top]}


if __name__ == "__main__":
    got = read(sys.argv[1])
    per = int(sys.argv[2]) if len(sys.argv) > 2 else None
    print(dataclasses.asdict(got) if per is None else breakdown(got, per))
