"""From a profiler trace to numbers: device busy time, idle share, time per
device op, collective time, and idle gaps attributed to the client's host
spans.

The window is the client's own: from the start of its first host span to
the end of its last (spans named ``bench.*``, written by
``jax.profiler.TraceAnnotation``).  A device's busy time is the union of
the intervals of its ops (the ``XLA Ops`` line of each ``/device:`` plane)
clipped to the window; busy and op times are averaged over the devices.
An op's time includes the ops nested in it (a ``conditional`` or ``while``
holds its branches' ops), so op times overlap and do not add up to busy.
An idle gap of device 0 is named by the host span it overlaps most, or
``other``.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import pathlib

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
COLLECTIVE = "all-to-all"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                    # mean over devices
    devices: int
    ops_s: dict                      # op name -> seconds (mean over devices)
    all_to_all_s: float              # mean over devices
    idle_by_span: dict               # host span name -> idle seconds (dev 0)
    spans: dict                      # host span name -> count in window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find(trace_dir) -> pathlib.Path:
    """The one ``.xplane.pb`` file a trace directory holds."""
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {len(files)}")
    return files[0]


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo, hi) -> list:
    """The complement of merged ``busy`` in ``[lo, hi)``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def short(name: str) -> str:
    """An op's name without its HLO text: ``%fusion.9 = ...`` -> ``fusion.9``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield short(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns


def reduce(profile) -> Reduction:
    """Reduce a ``jax.profiler.ProfileData``."""
    host, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            ops = list(_events(plane, OPS_LINE))
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            host += [e for e in _events(plane)
                     if e[0].startswith(SPAN_PREFIX)]
    if not host:
        raise RuntimeError("the trace holds no client spans")
    if not devices:
        raise RuntimeError("the trace holds no device ops")
    lo, hi = min(s for _, s, _ in host), max(e for _, _, e in host)
    busy, ops_s, a2a, per_dev_busy = 0.0, collections.Counter(), 0.0, []
    for ops in devices:
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        per_dev_busy.append(merged)
        busy += sum(e - s for s, e in merged)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                ops_s[name] += d
                if name.startswith(COLLECTIVE):
                    a2a += d
    n = len(devices)
    idle = collections.Counter()
    spans = sorted((s, e, name) for name, s, e in host)
    first = 0                    # the client's spans follow one another
    for g0, g1 in gaps(per_dev_busy[0], lo, hi):
        while first < len(spans) and spans[first][1] <= g0:
            first += 1
        best, who = 0, "other"
        for s, e, name in itertools.islice(spans, first, None):
            if s >= g1:
                break
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, who = ov, name
        idle[who] += g1 - g0
    return Reduction(
        window_s=(hi - lo) * 1e-9, busy_s=busy / n * 1e-9, devices=n,
        ops_s={k: v / n * 1e-9 for k, v in ops_s.items()},
        all_to_all_s=a2a / n * 1e-9,
        idle_by_span={k: v * 1e-9 for k, v in idle.items()},
        spans=dict(collections.Counter(name for _, _, name in spans)))


def read(trace_dir) -> Reduction:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(str(find(trace_dir))))


def breakdown(r: Reduction, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line."""
    def most(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]
    return {"device_ops": most(r.ops_s), "idle_gaps": most(r.idle_by_span)}
