"""The four-chip cell rehearsed on four virtual CPU devices through the
harness (a subprocess, so the device-count flag never reaches other
tests): correct as the program is; not correct with the 8-bit fingerprint
control in its place, routed to each key's owner shard; and not correct
with each fault of ``test_bench_faults.py`` planted on the routed path."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from bench import cells

SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json, time
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from bench import cells, control, harness, roofline
from bench.tests import rehearsal, test_bench_faults as faults

NAME, MODE = sys.argv[1], sys.argv[2]
svc = cells.module("services", "routed_step")
AXIS = svc.AXIS


def routed_fingerprint(st, keys, *, owner, bits):
    # control.fingerprint_lookup on each key's owner shard, routed there
    # and back as the program routes its own lookups
    from repro.core import distributed as dd
    s = jax.lax.axis_size(AXIS)
    rt = dd._owner_route(keys, s, owner, None, None)
    rk = dd._exchange(rt.send, AXIS).reshape(-1)
    rm = dd._exchange(rt.smask, AXIS).reshape(-1)
    f, v = control.fingerprint_lookup(dd.peel(st), rk, bits=bits)
    rf = dd._exchange((f & rm).reshape(s, -1), AXIS)
    rv = dd._exchange(v.reshape(s, -1), AXIS)
    return (dd._unroute(rf, rt, fill=False).astype(bool),
            dd._unroute(rv, rt, fill=0))


class RoutedFingerprint(control.Fingerprint):
    def __init__(self, inner, bits=8):
        super().__init__(inner, bits)
        sh = inner.sharded.state.cursor.sharding
        self.sharding = jax.sharding.NamedSharding(sh.mesh, P(AXIS))
        self.lookup = jax.jit(jax.shard_map(
            lambda st, k: routed_fingerprint(st, k, owner=svc.owner_hash(),
                                             bits=bits),
            mesh=sh.mesh, in_specs=(P(AXIS), P(AXIS)),
            out_specs=(P(AXIS), P(AXIS)), check_vma=False))

    def submit(self, b):
        k = jax.device_put(b.look, self.sharding)
        return self.lookup(self.inner.sharded.state, k), self.inner.submit(b)


class StateUnchanged(faults.StateUnchanged):
    # the stack's shards put back as they were, on their own devices
    def submit(self, b):
        eng = self.inner.sharded
        before = jax.tree_util.tree_map(jnp.copy, eng.state)
        h = self.inner.submit(b)
        eng.state = before
        return h


WRAPS = {"plain": None, "control": RoutedFingerprint,
         "StateUnchanged": StateUnchanged, "HalfBatch": faults.HalfBatch,
         "AnswerAltered": faults.AnswerAltered}
devs = jax.devices()[:4]
roofline.PEAKS[devs[0].device_kind] = roofline.PEAKS["TPU v5 lite"]
cell = dataclasses.replace(rehearsal.tiny(NAME), chips=4)
out = harness.run(cell, 2**31 + 17, 0.4, False, t_start=time.perf_counter(),
                  devices=devs, compiles=harness.Compiles(),
                  wrap=WRAPS[MODE])
print("RESULT " + json.dumps(out))
"""

NAME = "paper_mix_x4.rehash"


def _run(mode: str) -> dict:
    import json
    env = dict(os.environ, PYTHONPATH=f"src{os.pathsep}.")
    r = subprocess.run([sys.executable, "-c", SCRIPT, NAME, mode],
                       capture_output=True, text=True, env=env,
                       cwd=str(cells.ROOT))
    assert r.returncode == 0, r.stderr[-3000:]
    (line,) = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_routed_cell_runs_correct_on_four_devices():
    out = _run("plain")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 4


def test_routed_control_is_not_correct_on_four_devices():
    out = _run("control")
    assert not out["correct"]
    assert out["checks"]["found_wrong"]["value"] \
        + out["checks"]["value_wrong"]["value"] > 0


@pytest.mark.parametrize("fault", ["StateUnchanged", "HalfBatch",
                                   "AnswerAltered"])
def test_routed_fault_is_not_correct_on_four_devices(fault):
    out = _run(fault)
    assert not out["correct"], out["checks"]
