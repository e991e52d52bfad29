"""One run of one cell: build, warm up, measure, check, report.

Set-up runs from process start to the first timed batch: making the
mix's traffic and its service (the table built and filled, a rehash
started where the mix asks for one), and the mix's ``warmup_batches``
batches through the same client loop (which load or
compile every program the window runs, the engine's poll among them).
The window then runs for ``seconds``; with ``trace`` the profiler records
it and the per-layer metrics are read from the trace instead of the
end-to-end ones.  Once the window has closed and the device memory has
been read, the answers are compared with the model.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
import tempfile

from bench import cells as cells_
from bench import check, client, roofline
from bench import trace as trace_

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Reading:
    """What a metric reader (``bench/metrics/<name>.py``) reads."""

    window: client.Window
    trace: trace_.Reduction | None
    bytes_per_batch: int             # necessary HBM bytes, per chip
    peak: roofline.Peak
    setup_s: float
    bytes_in_use: int                # summed over the cell's chips
    live_keys: int


def reader(name: str):
    """The ``read`` function of metric ``name``."""
    return cells_.module("metrics", name).read


def traffic(cell: cells_.Cell, seed: int):
    """The cell's traffic, made by the mix's generator."""
    gen = cells_.module("generators", cell.traffic["generator"])
    return gen.Traffic(cell.traffic, cell.config["keys"], seed,
                       shards=cell.chips)


class Compiles:
    """Backend compilations seen in this process."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.count += 1


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def run(cell: cells_.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices, compiles: Compiles, wrap=None) -> dict:
    """One run; returns the result line's object.  ``wrap`` puts another
    service in the program's place (the control, a planted fault)."""
    import jax

    mix = cell.traffic
    batches = traffic(cell, seed)
    svc, facts = cells_.module("services", mix["service"]).build(
        cell, batches, seed)
    if wrap is not None:
        svc = wrap(svc)
    emit(phase="built", cell=cell.name, **facts)
    record = client.Record(client.Reservoir(mix["checked_batches"], seed))
    warm = client.drive(svc, batches, record, batches=mix["warmup_batches"])
    emit(phase="warmed", batches=warm.batches, compiles=compiles.count)
    before = compiles.count
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(tmp)
        win = client.drive(svc, batches, record, seconds=seconds,
                           annotate=trace)
        if trace:
            jax.profiler.stop_trace()
        reduced = trace_.read(tmp) if trace else None
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devices]
    in_use = sum(s.get("bytes_in_use", 0) for s in stats)
    peak_mem = max(s.get("peak_bytes_in_use", 0) for s in stats)
    emit(phase="window", batches=win.batches, seconds=win.seconds,
         compiles=compiles.count - before, bytes_in_use=in_use)
    counted = svc.live()
    del svc
    checks, live = check.compare(batches, record, facts["populate_acked"],
                                 counted)
    kind = devices[0].device_kind
    shape = roofline.TableShape(
        **{k: facts[k] for k in ("slots", "max_probes", "chunk")})
    necessary = roofline.batch_bytes(shape, batches.ops, cell.config["keys"],
                                     facts["rehash"])
    reading = Reading(win, reduced, necessary,
                      roofline.peak(kind), win.t0 - t_start, in_use, live)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = reader(m["name"])(reading)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_mem}
    out = {"correct": check.passed(checks), "attempted": win.ops,
           "failed": sum(v for k, (v, _) in checks.items() if k != "live_gap"),
           "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        out["breakdown"] = trace_.breakdown(reduced)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    return out
