"""Table-stack tests: ``dhash.make_stack`` + the vmapped ``stack_*`` ops.

The contract under test: a stack of T tables behaves EXACTLY like T
independently-run tables — lookup/insert/delete results, rebuild progress,
and epoch counters all match a Python loop over the unstacked states, with
rebuild epochs fully staggered across the stack — while the fused
1-sort/1-pallas_call budget holds per table step (vmap batches the kernel
launch over [T] instead of re-issuing it T times).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, dhash
from repro.core.engine import DHashStackEngine

T = 8          # acceptance: >= 8 tables
CAP = 384
Q = 64

ALL_BACKENDS = backend.names()
FUSED_AXIS = [(b, f) for b in ALL_BACKENDS
              for f in ((False, True) if backend.get(b).fused else (False,))]


def _count_primitives(closed_jaxpr, names):
    from collections import Counter
    ctr = Counter()

    def rec(jaxpr):
        for eq in jaxpr.eqns:
            ctr[eq.primitive.name] += 1
            for p in eq.params.values():
                if hasattr(p, "jaxpr"):
                    rec(p.jaxpr if hasattr(p.jaxpr, "eqns") else p.jaxpr.jaxpr)

    rec(closed_jaxpr.jaxpr)
    return {n: ctr.get(n, 0) for n in names}


def _keys(rng, t=T, n=CAP):
    return jnp.asarray(rng.choice(1_000_000, (t, n), replace=False)
                       .astype(np.int32)) + 1


def test_make_stack_shape_and_unstack():
    st = dhash.make_stack(T, "linear", CAP, chunk=64, seed=0)
    assert dhash.stack_size(st) == T
    assert st.hazard_key.shape == (T, 64)
    singles = dhash.unstack(st)
    assert len(singles) == T
    # per-table seeds are decorrelated: hash functions differ across tables
    seeds = {tuple(np.asarray(s.old.hfn.seeds).tolist()) for s in singles}
    assert len(seeds) == T
    # unstack inverts the stack exactly
    restacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *singles)
    for a, b in zip(jax.tree_util.tree_leaves(st),
                    jax.tree_util.tree_leaves(restacked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        dhash.make_stack(0, "linear", CAP)


@pytest.mark.parametrize("name,fused", FUSED_AXIS)
def test_stack_parity_vs_independent_loop(name, fused):
    """The acceptance walk: a T-table stack through insert / staggered
    rebuild epochs / mid-epoch lookup+delete / epoch swaps matches T
    independently-run tables step for step."""
    rng = np.random.default_rng(7)
    st = dhash.make_stack(T, name, CAP, chunk=128, seed=0, fused=fused)
    singles = dhash.unstack(st)
    keys = _keys(rng)
    vals = keys * 5

    ins_s = jax.jit(dhash.stack_insert)
    ins_1 = jax.jit(dhash.insert)
    st, ok = ins_s(st, keys[:, :CAP // 2], vals[:, :CAP // 2])
    for i in range(T):
        singles[i], ok1 = ins_1(singles[i], keys[i, :CAP // 2],
                                vals[i, :CAP // 2])
        np.testing.assert_array_equal(np.asarray(ok[i]), np.asarray(ok1))

    # STAGGERED epochs: every second table starts rebuilding now, the rest
    # stay on the fast path; two of them join three steps later
    mask0 = jnp.asarray([i % 2 == 0 for i in range(T)])
    st = jax.jit(dhash.stack_autostart)(st, mask0)
    auto_1 = jax.jit(dhash.rebuild_autostart)
    for i in range(0, T, 2):
        singles[i] = auto_1(singles[i])

    step_s = jax.jit(lambda d: dhash.stack_finish_same_shape(
        dhash.stack_rebuild_step(d)))
    step_1 = jax.jit(lambda d: dhash.finish_same_shape(dhash.rebuild_step(d)))
    lk_s, lk_1 = jax.jit(dhash.stack_lookup), jax.jit(dhash.lookup)
    del_s, del_1 = jax.jit(dhash.stack_delete), jax.jit(dhash.delete)

    dels = keys[:, :Q]
    ep_trace = []
    for step in range(24):
        if step == 3:
            mask1 = jnp.asarray([i in (1, 3) for i in range(T)])
            st = jax.jit(dhash.stack_autostart)(st, mask1)
            singles[1] = auto_1(singles[1])
            singles[3] = auto_1(singles[3])
        st = step_s(st)
        f, v = lk_s(st, keys[:, :Q])
        if step == 5:
            st, okd = del_s(st, dels)
        for i in range(T):
            singles[i] = step_1(singles[i])
            f1, v1 = lk_1(singles[i], keys[i, :Q])
            np.testing.assert_array_equal(np.asarray(f[i]), np.asarray(f1))
            np.testing.assert_array_equal(np.asarray(v[i]), np.asarray(v1))
            if step == 5:
                singles[i], okd1 = del_1(singles[i], dels[i])
                np.testing.assert_array_equal(np.asarray(okd[i]),
                                              np.asarray(okd1))
        ep_trace.append(np.asarray(st.epoch).copy())

    # epochs are independent AND staggered: started tables progressed
    # exactly like their independent twins, never-started tables are
    # untouched, and at some point mid-run the early starters were a full
    # epoch ahead of the late ones
    ep_s = np.asarray(st.epoch)
    ep_1 = np.array([int(s.epoch) for s in singles])
    np.testing.assert_array_equal(ep_s, ep_1)
    np.testing.assert_array_equal(np.asarray(st.rebuilding),
                                  np.array([bool(s.rebuilding)
                                            for s in singles]))
    started = [i for i in range(T) if i % 2 == 0 or i in (1, 3)]
    idle = [i for i in range(T) if i not in started]
    assert (ep_s[idle] == 0).all()
    assert (ep_s[started] >= 1).all(), "started rebuilds must complete"
    assert any(len(set(ep[started])) > 1 for ep in ep_trace), \
        "staggered starts should spread epochs across the stack mid-run"

    # final contents match per table
    cnt_s = np.asarray(jax.jit(dhash.stack_count_items)(st))
    cnt_1 = np.array([int(dhash.count_items(s)) for s in singles])
    np.testing.assert_array_equal(cnt_s, cnt_1)


@pytest.mark.parametrize("name", [b for b in ALL_BACKENDS
                                  if backend.get(b).fused])
def test_stack_fused_budget_per_table_step(name):
    """The acceptance budget: the whole stack's rebuild-epoch ordered
    lookup — and the fast-path fused lookup — stay ONE pallas_call (plus
    the sorted-slab kernels' one sort; the windowed linear kernels need
    none) under vmap (the launch is batched over [T], not re-issued per
    table)."""
    be = backend.get(name)
    budget = {"sort": 0 if name == "linear" else 1, "pallas_call": 1}
    st = dhash.make_stack(T, name, CAP, chunk=64, seed=0, fused=True)
    keys = _keys(np.random.default_rng(3), n=Q)

    ordered = jax.vmap(lambda d, k: be.ordered_lookup_fused(
        d.old, d.new, d.hazard_key, d.hazard_val, d.hazard_live, k,
        nres_cap=d.nres_cap))
    counts = _count_primitives(jax.make_jaxpr(ordered)(st, keys),
                               ("sort", "pallas_call"))
    assert counts == budget, (name, counts)

    fast = jax.vmap(lambda d, k: be.lookup_fused(d.old, k))
    counts = _count_primitives(jax.make_jaxpr(fast)(st, keys),
                               ("sort", "pallas_call"))
    assert counts == budget, (name, counts)


def test_stack_engine_continuous_rebuild():
    """DHashStackEngine: the vmapped step loop sustains per-table op
    batches through continuous independent rebuilds and reports aggregate
    epoch progress."""
    rng = np.random.default_rng(0)
    eng = DHashStackEngine(dhash.make_stack(T, "linear", 128, chunk=32,
                                            seed=0),
                           continuous_rebuild=True, poll_every=4)
    keys = _keys(rng, n=128)
    none_i = np.zeros((T, 1), np.int32)
    for j in range(0, 128, 32):
        eng.step(keys[:, j:j + 32], keys[:, j:j + 32], keys[:, j:j + 32] * 3,
                 none_i, del_mask=np.zeros((T, 1), bool))
    for _ in range(30):
        f, v, _, _ = eng.step(keys[:, :32], none_i, none_i, none_i,
                              ins_mask=np.zeros((T, 1), bool),
                              del_mask=np.zeros((T, 1), bool))
    assert bool(np.asarray(f).all())
    np.testing.assert_array_equal(np.asarray(v), np.asarray(keys[:, :32]) * 3)
    np.testing.assert_array_equal(eng.counts(), np.full(T, 128))
    assert eng.stats.rebuilds_completed >= T, \
        "continuous mode should complete epochs on every table"


def test_stack_engine_masked_request_rebuild():
    eng = DHashStackEngine(dhash.make_stack(4, "twochoice", 256, chunk=32,
                                            seed=0))
    eng.request_rebuild(np.array([True, False, True, False]))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(eng.state.rebuilding)),
        np.array([True, False, True, False]))
