"""The program names its own work in a profiler trace: the device ops of
every DHash operation carry its ``jax.named_scope`` in their HLO
``op_name`` metadata, and each engine call is a host span with its
transfers, dispatch and poll as children."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dhash
from repro.core.engine import DHashEngine, DHashStackEngine

I32 = np.int32
SCOPES = {"dhash.lookup", "dhash.insert", "dhash.delete", "dhash.hazard",
          "dhash.rebuild_step", "dhash.finish_same_shape",
          "dhash.rebuild_autostart"}


def _scopes(compiled) -> set:
    """The scopes named in a compiled program's ``op_name`` metadata."""
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return {s for s in SCOPES for n in names
            if re.search(r"(^|[/(])" + re.escape(s) + r"($|[/)])", n)}


def _operands(shape):
    k = jnp.arange(int(np.prod(shape)), dtype=I32).reshape(shape)
    m = jnp.ones(shape, bool)
    return k, k, k, k, m, m


# the plain engine's step holds no epoch swap or rebuild start: the poll
# runs them, under the host span ``dhash.engine.swap``
STEP_SCOPES = SCOPES - {"dhash.finish_same_shape", "dhash.rebuild_autostart"}


@pytest.mark.parametrize("kind,kw,want", [
    ("jnp", {}, STEP_SCOPES),
    ("fwd_hazard", {"fwd_hazard": True}, STEP_SCOPES),
    # the fused ordered probe and delete do the hazard check in-kernel
    ("fused", {"fused": True}, STEP_SCOPES - {"dhash.hazard"}),
])
def test_engine_step_hlo_names_every_operation(kind, kw, want, tmp_path):
    keys = np.arange(64, dtype=I32)
    d, _ = jax.jit(dhash.insert)(
        dhash.make("linear", capacity=64, chunk=32, seed=3, **kw), keys, keys)
    eng = DHashEngine(d, continuous_rebuild=True, poll_every=4)
    fn = eng._step_fn
    assert _scopes(fn.lower(eng.state, *_operands((64,))).compile()) == want
    # 128 slots in 4 chunks, each holding some of the 64 keys: the rebuild
    # started at construction takes 8 transitions, so the poll of step 8
    # swaps the epochs, and the next rebuild ends after step 16
    off = np.zeros(64, bool)
    eng.step(keys, keys, keys, keys, off, off)   # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(9):                    # steps 2..10: polls at 4, 8
            eng.step(keys, keys, keys, keys, off, off)
        jax.block_until_ready(eng.state)
    spans = _host_spans(tmp_path)
    polls = [s for s in spans if s[2] == "dhash.engine.poll"]
    swaps = [s for s in spans if s[2] == "dhash.engine.swap"]
    assert len(polls) == 2 and len(swaps) == 1
    assert _inside(swaps[0], polls[1:]) == 1
    assert eng.stats.rebuilds_completed == 1


ROUTED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, re
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.core import distributed as dd, hashing
from repro.core.engine import routed_step_fn

sh = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("shard",)), P("shard"))
st = dd.make_stacked(4, "linear", capacity=64, chunk=32, seed=3, sharding=sh)
k = jnp.arange(256, dtype=jnp.int32)
m = jnp.ones(256, bool)
txt = routed_step_fn(sh, hashing.fresh("mix32", 1)).lower(
    st, k, k, k, k, m, m).compile().as_text()
a2a = [l for l in txt.splitlines() if re.search(r"\ball-to-all\(", l)]
print("NAMES " + json.dumps(sorted(set(re.findall(r'op_name="([^"]*)"', txt)))))
print("A2A " + json.dumps([re.search(r'op_name="([^"]*)"', l).group(1)
                           for l in a2a]))
"""


def test_routed_step_hlo_names_route_and_exchange():
    """The sharded engine's step (four virtual devices, in a subprocess)
    names the router's layout work ``dhash.route`` and its all-to-alls
    ``dhash.exchange``, beside every operation of the plain step."""
    root = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run([sys.executable, "-c", ROUTED_SCRIPT],
                       capture_output=True, text=True, cwd=root,
                       env=dict(os.environ, PYTHONPATH="src"))
    assert r.returncode == 0, r.stderr[-3000:]
    out = dict(line.split(" ", 1) for line in r.stdout.splitlines()
               if line.startswith(("NAMES ", "A2A ")))
    routed = {"dhash.route", "dhash.exchange"}
    got = {s for s in SCOPES | routed for n in json.loads(out["NAMES"])
           if re.search(r"(^|[/(])" + re.escape(s) + r"($|[/)])", n)}
    assert got == STEP_SCOPES | routed
    a2a = json.loads(out["A2A"])
    assert len(a2a) == 11 and all("dhash.exchange" in n for n in a2a)


def test_lookup_hlo_names_lookup_and_hazard():
    d = dhash.make("linear", capacity=256, chunk=32, seed=3)
    keys = jnp.arange(64, dtype=I32)
    got = _scopes(jax.jit(dhash.lookup).lower(d, keys).compile())
    assert got == {"dhash.lookup", "dhash.hazard"}
    counted = jax.jit(dhash.lookup_counted).lower(d, keys).compile()
    assert _scopes(counted) == {"dhash.lookup", "dhash.hazard"}


def test_stack_step_hlo_names_every_operation():
    eng = DHashStackEngine(dhash.make_stack(2, "linear", capacity=256,
                                            chunk=32, seed=3),
                           continuous_rebuild=True)
    compiled = eng._step_fn.lower(eng.state, *_operands((2, 64))).compile()
    assert _scopes(compiled) == SCOPES


def _host_spans(trace_dir) -> list:
    """``(start, end, name, stats)`` of every ``dhash.*`` host event."""
    from jax.profiler import ProfileData
    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dhash."):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, dict(ev.stats)))
    return sorted(out)


def _inside(child, parents) -> int:
    return sum(1 for p in parents if p[0] <= child[0] and child[1] <= p[1])


@pytest.mark.parametrize("stacked", [False, True], ids=["engine", "stack"])
def test_engine_spans_in_profiler_trace(stacked, tmp_path):
    if stacked:
        eng = DHashStackEngine(dhash.make_stack(2, "linear", capacity=256,
                                                chunk=32, seed=3),
                               continuous_rebuild=True, poll_every=4)
        keys = np.arange(128, dtype=I32).reshape(2, 64)
    else:
        eng = DHashEngine(dhash.make("linear", capacity=256, chunk=32,
                                     seed=3),
                          continuous_rebuild=True, poll_every=4)
        keys = np.arange(64, dtype=I32)
    eng.step(keys, keys, keys, keys)          # compile outside the trace
    jax.block_until_ready(eng.lookup(keys))
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(9):                    # steps 2..10: polls at 4, 8
            eng.step(keys, keys, keys, keys)
        jax.block_until_ready(eng.lookup(keys))
    spans = _host_spans(tmp_path)

    def named(n):
        return [s for s in spans if s[2] == n]

    steps, polls = named("dhash.engine.step"), named("dhash.engine.poll")
    assert [s[3]["step"] for s in steps] == list(range(1, 10))
    assert len(polls) == 2
    assert all(_inside(p, steps) == 1 for p in polls)
    (lookup,) = named("dhash.engine.lookup")
    for child in ("dhash.engine.put", "dhash.engine.dispatch"):
        got = named(child)
        assert len(got) == 10
        assert sum(_inside(c, steps) for c in got) == 9
        assert sum(_inside(c, [lookup]) for c in got) == 1
