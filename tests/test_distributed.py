"""Distributed DHash: routed ops on an 8-device host mesh (subprocess, so
the 8-device XLA flag never leaks into other tests)."""
from __future__ import annotations

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
import jax.tree_util as jtu
from functools import partial
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import dhash, distributed as dd, hashing

mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(8), ("model",))
owner = hashing.fresh("tabulation", 7)
whole = dd.make_stacked(8, "linear", capacity=256, chunk=64, seed=0)
tspec = jtu.tree_map(lambda _: P("model"), dhash.make("linear", 256, chunk=64))
# built shard by shard on the devices that hold them: same tables, each
# device holding only its own
stacked = dd.make_stacked(8, "linear", capacity=256, chunk=64, seed=0,
                          sharding=NamedSharding(mesh, P("model")))
assert jtu.tree_structure(stacked) == jtu.tree_structure(whole)
for a, b in zip(jtu.tree_leaves(stacked), jtu.tree_leaves(whole)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [s.device for s in a.addressable_shards] == list(mesh.devices)
    assert all(s.data.shape == (1,) + b.shape[1:] for s in a.addressable_shards)

keys = jnp.arange(1, 513, dtype=jnp.int32)
vals = keys * 3

@partial(jax.shard_map, mesh=mesh, check_vma=False,
         in_specs=(tspec, P("model"), P("model"), P("model"), P("model")),
         out_specs=(tspec, P("model")))
def service(dstack, lk, ik, iv, dk):
    d = dd.peel(dstack)
    d, (found, _, ok_i, ok_d), load = dd.routed_service_step(
        d, lk, ik, iv, dk, jnp.ones(ik.shape, bool), jnp.ones(dk.shape, bool),
        "model", owner)
    stats = jnp.stack([x.sum(dtype=jnp.int32) for x in (found, ok_i, ok_d)])
    return dd.unpeel(d), jnp.concatenate([stats, load])[None]

# step 1: insert everything (lookups miss), step 2: all lookups hit
z = jnp.zeros((8,), jnp.int32)
stacked, stats = jax.jit(service)(stacked, keys, keys, vals, z)
stats = np.asarray(stats)
assert int(stats[:, 1].sum()) == 512, stats       # every insert acked
# each device's load row counts its 64 lookups + 64 inserts + 1 delete
np.testing.assert_array_equal(stats[:, 3:].sum(axis=1), 64 + 64 + 1)
stacked, stats = jax.jit(service)(stacked, keys, z, z, z)
found_total = int(np.asarray(stats)[:, 0].sum())
assert found_total == 512, found_total

# a capped lookup rides the overflow-proof slab: every key is answered;
# a compact slab drops keys, and the route it returns counts each one
def lookup_capped(cap, slack):
    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(tspec, P("model")),
             out_specs=(P("model"), P("model"), P("model")))
    def fn(dstack, lk):
        f, v, rt = dd.routed_lookup(dd.peel(dstack), lk, "model", owner,
                                    cap=cap, spill_slack=slack)
        return f, v, rt.dropped[None]
    return jax.jit(fn)

f, v, dropped = lookup_capped(32, None)(stacked, keys)
f, v = np.asarray(f), np.asarray(v)
assert f.all() and int(np.asarray(dropped).sum()) == 0
assert (v == np.asarray(keys) * 3).all()
f, v, dropped = lookup_capped(4, 0.01)(stacked, keys)
f, dropped = np.asarray(f), int(np.asarray(dropped).sum())
assert dropped > 0 and f.sum() == 512 - dropped, (f.sum(), dropped)
assert (np.asarray(v)[f] == np.asarray(keys)[f] * 3).all()

# shard-local rebuild with synchronized epochs: all data survives
for _ in range(64):
    stacked, _ = jax.jit(service)(stacked, z, z, z, z)  # rebuild_step x64

print("DIST-OK")
"""


def test_distributed_dhash_8dev():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DIST-OK" in r.stdout


# -- the S×T grid: routed stack ops over mesh-sharded tenant stacks ----------
SCRIPT_GRID = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
import jax.tree_util as jtu
from functools import partial
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import backend, dhash, distributed as dd, hashing

S, T, QL = 2, 3, 48                      # 2 shards x 3 tenants, 48 queries/shard
mesh = jax.sharding.Mesh(np.array(jax.devices()[:S]), ("grid",))
owner = hashing.fresh("tabulation", 7)
rng = np.random.default_rng(0)
keys = jnp.asarray(rng.choice(100_000, S * QL, replace=False).astype(np.int32)) + 1
tenant = jnp.asarray(rng.integers(0, T, S * QL).astype(np.int32))
vals = keys * 5
own_np = np.asarray(dd.grid_owner(keys, tenant, S, T, owner))

for name in backend.names():
    for fused in ((False, True) if backend.get(name).fused else (False,)):
        full = dhash.make_stack(S * T, name, 128, chunk=64, seed=5, fused=fused)
        grid = jtu.tree_map(lambda x: x.reshape((S, T) + x.shape[1:]), full)
        gspec = jtu.tree_map(lambda _: P("grid"), grid)
        sh = lambda x: jax.device_put(x, NamedSharding(mesh, P("grid")))
        grid = jtu.tree_map(sh, grid)

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=(gspec, P("grid"), P("grid"), P("grid")),
                 out_specs=(gspec, P("grid"), P("grid")))
        def g_insert(g, k, v, tn):
            d = dd.peel(g)
            d, ok, ov = dd.routed_stack_update(
                d, k, v, jnp.ones(k.shape, bool), tn, "grid", owner,
                op=dhash.stack_insert, cap_factor=0.0)
            return dd.unpeel(d), ok, ov[None]

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=(gspec, P("grid"), P("grid")),
                 out_specs=(P("grid"), P("grid"), P("grid")))
        def g_lookup(g, k, tn):
            f, v, ov = dd.routed_stack_lookup(
                dd.peel(g), k, tn, "grid", owner, cap_factor=0.0)
            return f, v, ov[None]

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=(gspec, P("grid")), out_specs=gspec)
        def g_autostart(g, m):
            return dd.unpeel(dhash.stack_autostart(dd.peel(g), m[0]))

        @partial(jax.shard_map, mesh=mesh, check_vma=False,
                 in_specs=(gspec,), out_specs=gspec)
        def g_step(g):
            return dd.unpeel(dhash.stack_finish_same_shape(
                dhash.stack_rebuild_step(dd.peel(g))))

        grid, ok, ov = jax.jit(g_insert)(grid, keys, vals, tenant)
        assert bool(np.asarray(ok).all()), (name, fused, "insert dropped keys")
        assert int(np.asarray(ov).sum()) == 0

        # staggered epochs: (shard 0, tenant 0) and (shard 1, tenant 2) only
        started = np.array([[True, False, False], [False, False, True]])
        grid = jax.jit(g_autostart)(grid, jnp.asarray(started))
        lk = jax.jit(g_lookup)
        st = jax.jit(g_step)
        for step in range(16):
            grid = st(grid)
            if step in (0, 7, 15):     # mid-rebuild resolution never blocks
                f, v, _ = lk(grid, keys, tenant)
                assert bool(np.asarray(f).all()), (name, fused, step)
                np.testing.assert_array_equal(np.asarray(v), np.asarray(vals))
        ep = np.asarray(jax.device_get(grid.epoch))
        np.testing.assert_array_equal(ep, started.astype(ep.dtype))
        reb = np.asarray(jax.device_get(grid.rebuilding))
        assert not reb.any(), (name, fused, "rebuilds must complete")

        # parity vs the single-device stack_* ops on the SAME final tables
        merged = jtu.tree_map(
            lambda x: jnp.reshape(jax.device_get(x), (S * T,) + x.shape[2:]),
            grid)
        rt = dd._route(keys, jnp.asarray(own_np), S * T)
        f1, v1 = dhash.stack_lookup(merged, rt.send, rt.smask)
        f, v, _ = lk(grid, keys, tenant)
        np.testing.assert_array_equal(
            np.asarray(f), np.asarray(dd._unroute(f1, rt, fill=False)))
        np.testing.assert_array_equal(
            np.asarray(v)[np.asarray(f)],
            np.asarray(dd._unroute(v1, rt, fill=0))[np.asarray(f)])

print("GRID-PARITY-OK")

# adversarial all-keys-one-tenant batch on the CAPPED path: the
# overflow-proof spill slab serves EVERY key in the single pass (no retry
# exists any more) while the overflow counters stay exact per shard-local
# batch
def fresh_grid(seed):
    g = jtu.tree_map(lambda x: x.reshape((S, T) + x.shape[1:]),
                     dhash.make_stack(S * T, "linear", 128, chunk=64,
                                      seed=seed, fused=True))
    return jtu.tree_map(lambda x: jax.device_put(
        x, NamedSharding(mesh, P("grid"))), g)

grid = fresh_grid(9)
gspec = jtu.tree_map(lambda _: P("grid"), grid)
akeys = jnp.asarray(rng.choice(100_000, S * QL, replace=False)
                    .astype(np.int32)) + 200_000
atn = jnp.ones((S * QL,), jnp.int32)            # 100% skew: tenant 1
CF = 2.0
cap = dd.route_cap(CF, QL, S * T)

def make_capped(slack):
    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(gspec, P("grid"), P("grid"), P("grid")),
             out_specs=(gspec, P("grid"), P("grid")))
    def g_ins(g, k, v, tn):
        d = dd.peel(g)
        d, ok, ov = dd.routed_stack_update(
            d, k, v, jnp.ones(k.shape, bool), tn, "grid", owner,
            op=dhash.stack_insert, cap_factor=CF, spill_slack=slack)
        return dd.unpeel(d), ok, ov[None]

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(gspec, P("grid"), P("grid")),
             out_specs=(P("grid"), P("grid"), P("grid")))
    def g_lk(g, k, tn):
        f, v, ov = dd.routed_stack_lookup(
            dd.peel(g), k, tn, "grid", owner, cap_factor=CF,
            spill_slack=slack)
        return f, v, ov[None]
    return g_ins, g_lk

g_insert_capped, g_lookup_capped = make_capped(None)
grid, ok, ov = jax.jit(g_insert_capped)(grid, akeys, akeys * 5, atn)
ok, ov = np.asarray(ok), np.asarray(ov)
aown = np.asarray(dd.grid_owner(akeys, atn, S, T, owner))
exp_ov = np.stack([np.maximum(np.bincount(
    aown[i * QL:(i + 1) * QL], minlength=S * T) - cap, 0) for i in range(S)])
np.testing.assert_array_equal(ov, exp_ov)       # EXACT per-owner overflow
assert exp_ov.sum() > 0, "adversarial batch must overflow the cap"
assert ok.sum() == S * QL, "overflow-proof slab must serve every key"

@partial(jax.shard_map, mesh=mesh, check_vma=False,
         in_specs=(gspec, P("grid"), P("grid")),
         out_specs=(P("grid"), P("grid"), P("grid")))
def g_lookup_full(g, k, tn):
    f, v, ov = dd.routed_stack_lookup(
        dd.peel(g), k, tn, "grid", owner, cap_factor=0.0)
    return f, v, ov[None]

f, v, _ = jax.jit(g_lookup_full)(grid, akeys, atn)
f = np.asarray(f)
assert f.all(), "every slab-served insert must be visible full-width"
np.testing.assert_array_equal(np.asarray(v), np.asarray(akeys * 5))
print("GRID-CAP-OK")

# compact slab: slab-exhausted keys are EXACTLY accounted (ok=False per
# key, never silently lost) and the table holds precisely the served set
SL = 0.125
spill_cap = dd.route_spill_cap(QL, cap, SL)
assert 0 < spill_cap < QL - cap
grid2 = fresh_grid(11)
g_insert_compact, _ = make_capped(SL)
grid2, ok2, _ = jax.jit(g_insert_compact)(grid2, akeys, akeys * 5, atn)
ok2 = np.asarray(ok2)
exp_served = np.array([QL - max(int(exp_ov[i].sum()) - spill_cap, 0)
                       for i in range(S)])
assert (exp_served < QL).any(), "compact slab must actually drop"
np.testing.assert_array_equal(ok2.reshape(S, QL).sum(axis=1), exp_served)
f2, v2, _ = jax.jit(g_lookup_full)(grid2, akeys, atn)
f2 = np.asarray(f2)
np.testing.assert_array_equal(f2, ok2)          # present iff served
np.testing.assert_array_equal(np.asarray(v2)[f2], np.asarray(akeys * 5)[f2])
print("GRID-DROP-OK")

# jaxpr pins: the routed slab ops stay SINGLE-PASS inside shard_map —
# byte-for-byte the same primitive counts as the full-width
# (cap_factor=0.0) ops, so the slab adds NO pass on top of the
# mid-rebuild-ordered kernels' own structure (the bare-kernel
# kernel budget pin lives in test_routing.py where the op IS the bare
# fused lookup).  The retry cond is gone: insert lowers with ZERO conds,
# and so does lookup — the windowed linear kernels have no fallback
# branch, and vmap turns stack_lookup's ``d.rebuilding`` ordering gate
# (dhash.py) into a select, identical in the full-width reference.  Both ops keep ONE all_to_all pair per
# direction on the wire (lookup ships keys+mask out and found+vals
# back = 4; insert ships keys+mask+vals out and ok back = 4) — exactly
# the pre-slab wire count.
from collections import Counter
def prim_counts(fn, *xs):
    ctr = Counter()
    def rec(j):
        for eq in j.eqns:
            ctr[eq.primitive.name] += 1
            for p in eq.params.values():
                if hasattr(p, "eqns"):           # open Jaxpr (shard_map)
                    rec(p)
                elif hasattr(p, "jaxpr"):        # ClosedJaxpr (pjit, ...)
                    rec(p.jaxpr if hasattr(p.jaxpr, "eqns") else p.jaxpr.jaxpr)
    rec(jax.make_jaxpr(fn)(*xs).jaxpr)
    return ctr

@partial(jax.shard_map, mesh=mesh, check_vma=False,
         in_specs=(gspec, P("grid"), P("grid"), P("grid")),
         out_specs=(gspec, P("grid"), P("grid")))
def g_insert_fullwidth(g, k, v, tn):
    d = dd.peel(g)
    d, ok, ov = dd.routed_stack_update(
        d, k, v, jnp.ones(k.shape, bool), tn, "grid", owner,
        op=dhash.stack_insert, cap_factor=0.0)
    return dd.unpeel(d), ok, ov[None]

pairs = ((prim_counts(g_insert_capped, grid, akeys, akeys * 5, atn),
          prim_counts(g_insert_fullwidth, grid, akeys, akeys * 5, atn),
          "insert", 0),
         (prim_counts(g_lookup_capped, grid, akeys, atn),
          prim_counts(g_lookup_full, grid, akeys, atn),
          "lookup", 0))
for slab, fullw, tag, n_cond in pairs:
    assert slab == fullw, (tag, {k: (slab[k], fullw[k])
                                 for k in set(slab) | set(fullw)
                                 if slab[k] != fullw[k]})
    assert slab["cond"] == n_cond, (tag, slab["cond"])
    assert slab["all_to_all"] == 4, (tag, slab["all_to_all"])
print("GRID-JAXPR-OK")
"""


def test_routed_stack_grid_8dev():
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", SCRIPT_GRID],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    assert "GRID-PARITY-OK" in r.stdout
    assert "GRID-CAP-OK" in r.stdout
    assert "GRID-DROP-OK" in r.stdout
    assert "GRID-JAXPR-OK" in r.stdout
