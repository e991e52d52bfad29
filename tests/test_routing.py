"""Router unit tests: the two-pass counting-sort layout behind every
routed op.

The contract under test:

* ``_route`` produces the SAME owner-grouped [S, cap] layout as a stable
  reference (keys placed in batch order within their owner), with EXACT
  per-owner overflow counts — never a silent drop;
* route → unroute is the identity on kept keys, and dropped keys come back
  as an unmistakable fill (0/False for ints/bools, NaN for floats);
* the router lowers with ZERO ``sort`` primitives, so a routed fused
  ``stack_lookup`` keeps the single-op kernel budget:
  ONE sort + ONE pallas_call total (the fused kernel's own bucket sort is
  the only sort in the op).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend, dhash
from repro.core import distributed as dd

FUSED_BACKENDS = [b for b in backend.names() if backend.get(b).fused]


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


def _ref_route(keys, owner, nshards, cap):
    """Stable counting-sort reference in plain NumPy."""
    keys, owner = np.asarray(keys), np.asarray(owner)
    send = np.zeros((nshards, cap), keys.dtype)
    smask = np.zeros((nshards, cap), bool)
    kept = np.zeros(keys.shape[0], bool)
    fill = np.zeros(nshards, np.int64)
    for i in range(keys.shape[0]):
        o = int(owner[i])
        r = fill[o]
        fill[o] += 1
        if r < cap:
            send[o, r] = keys[i]
            smask[o, r] = True
            kept[i] = True
    return send, smask, kept, np.maximum(fill - cap, 0)


def test_route_cap_math():
    # cap = ceil(c*Q/S)
    assert dd.route_cap(2.0, 64, 8) == 16
    assert dd.route_cap(1.0, 64, 8) == 8
    assert dd.route_cap(1.0, 65, 8) == 9          # ceil, not floor
    assert dd.route_cap(2.0, 128, 64) == 4
    # <= 0 means the overflow-proof full width
    assert dd.route_cap(0.0, 64, 8) == 64
    assert dd.route_cap(-1.0, 64, 8) == 64
    # clamped to [1, Q]
    assert dd.route_cap(0.01, 4, 64) == 1
    assert dd.route_cap(100.0, 8, 2) == 8


def test_route_cap_exact_ceil_boundaries():
    """ceil(c·Q/S) is computed on the full product: the old
    ``int(c*q)`` idiom truncated the float product BEFORE the
    ceil-division, understating the cap whenever it carried a fraction."""
    # 1.1*9 = 9.9 -> ceil 10, then clamped to Q=9 (cap never exceeds Q)
    assert dd.route_cap(1.1, 9, 1) == 9
    # 1.25*10/4 = 3.125 -> 4 (old: int(12.5)=12 -> ceil(12/4)=3)
    assert dd.route_cap(1.25, 10, 4) == 4
    # 1.5*3/2 = 2.25 -> 3 (old: int(4.5)=4 -> ceil(4/2)=2)
    assert dd.route_cap(1.5, 3, 2) == 3
    # exact products are untouched by the fix
    assert dd.route_cap(2.0, 1024, 8) == 256
    assert dd.route_cap(2.0, 1024, 64) == 32
    assert dd.route_cap(2.0, 48, 6) == 16
    assert dd.route_cap(2.0, 16, 8) == 4


def test_route_spill_cap_math():
    # default (None): the overflow-proof bound Q - cap (total spill over
    # any batch is <= Q - cap, see the docstring's k-owner argument)
    assert dd.route_spill_cap(64, 16) == 48
    assert dd.route_spill_cap(64, 64) == 0        # cap >= Q: nothing spills
    assert dd.route_spill_cap(64, 100) == 0
    # slack budget: ceil(slack*Q), clamped to the overflow-proof bound
    assert dd.route_spill_cap(64, 16, 0.25) == 16
    assert dd.route_spill_cap(64, 16, 1.0) == 48  # >= 1: overflow-proof
    assert dd.route_spill_cap(64, 16, 5.0) == 48
    assert dd.route_spill_cap(64, 16, 0.001) == 1  # ceil, never 0 rounding
    assert dd.route_spill_cap(64, 16, 0.0) == 0   # <= 0 disables the slab
    assert dd.route_spill_cap(64, 16, -1.0) == 0
    assert dd.route_spill_cap(1024, 640, 0.375) == 384


@pytest.mark.parametrize("skew", ["uniform", "zipfish", "one_owner"])
def test_route_matches_stable_reference(skew):
    rng = np.random.default_rng(11)
    q, s = 96, 8
    keys = jnp.asarray(rng.choice(10_000, q, replace=False).astype(np.int32))
    if skew == "uniform":
        owner = rng.integers(0, s, q)
    elif skew == "zipfish":
        owner = np.minimum(rng.zipf(1.5, q) - 1, s - 1)
    else:
        owner = np.full(q, 3)
    owner = jnp.asarray(owner.astype(np.int32))
    for cap in (q, dd.route_cap(2.0, q, s), 3):
        rt = dd._route(keys, owner, s, cap)
        send, smask, kept, over = _ref_route(keys, owner, s, cap)
        np.testing.assert_array_equal(np.asarray(rt.send), send)
        np.testing.assert_array_equal(np.asarray(rt.smask), smask)
        np.testing.assert_array_equal(np.asarray(rt.kept), kept)
        np.testing.assert_array_equal(np.asarray(rt.overflow), over)
        # overflow is EXACT: hist - cap, never saturated or approximated
        hist = np.bincount(np.asarray(owner), minlength=s)
        np.testing.assert_array_equal(np.asarray(rt.overflow),
                                      np.maximum(hist - cap, 0))


def test_route_unroute_roundtrip_including_drops():
    rng = np.random.default_rng(5)
    q, s = 64, 4
    cap = dd.route_cap(1.0, q, s)                 # tight: guarantees drops
    keys = jnp.asarray(rng.choice(10_000, q, replace=False).astype(np.int32))
    owner = jnp.asarray((np.asarray(keys) * 7 % s).astype(np.int32))
    rt = dd._route(keys, owner, s, cap)
    assert int(rt.overflow.sum()) > 0, "cap must actually drop keys here"
    # a shard-side response derived from the routed keys round-trips to
    # batch order exactly on kept keys; dropped keys take the fill
    resp = rt.send * 3
    back = dd._unroute(resp, rt, fill=-1)
    expect = np.where(np.asarray(rt.kept), np.asarray(keys) * 3, -1)
    np.testing.assert_array_equal(np.asarray(back), expect)
    # payload scatter uses the same coordinates as the key scatter
    pay = dd._route_payload(keys * 3, rt)
    np.testing.assert_array_equal(np.asarray(pay), np.asarray(rt.send) * 3)
    # full-width route keeps EVERY key: round-trip is the identity
    full = dd._route(keys, owner, s)
    assert bool(np.asarray(full.kept).all())
    assert int(full.overflow.sum()) == 0
    np.testing.assert_array_equal(
        np.asarray(dd._unroute(full.send, full)), np.asarray(keys))


def test_unroute_float_fill_is_nan_safe():
    keys = jnp.arange(1, 9, dtype=jnp.int32)
    owner = jnp.asarray([0, 0, 0, 0, 1, 1, 1, 1], jnp.int32)
    rt = dd._route(keys, owner, 2, cap=2)         # half of each owner spills
    resp = rt.send.astype(jnp.float32) * 0.5
    back = dd._unroute(resp, rt)                  # default fill
    back = np.asarray(back)
    kept = np.asarray(rt.kept)
    # dropped float payloads are NaN — NEVER a fake 0.0
    assert np.isnan(back[~kept]).all()
    np.testing.assert_allclose(back[kept], np.arange(1, 9)[kept] * 0.5)
    # integer/bool responses default to 0/False instead
    backi = np.asarray(dd._unroute(rt.send, rt))
    assert (backi[~kept] == 0).all()


def test_router_lowers_with_zero_sorts():
    """The tentpole claim at the router level: the counting-sort layout
    contains NO ``sort`` primitive (pass 1 is a one-hot histogram +
    cumsum, pass 2 a 2-D scatter)."""
    keys = jnp.arange(128, dtype=jnp.int32)
    owner = keys % 8

    def route(k, o):
        rt = dd._route(k, o, 8, cap=32)
        return rt.send, rt.smask, rt.overflow

    counts = _count_primitives(jax.make_jaxpr(route)(keys, owner),
                               ("sort", "pallas_call"))
    assert counts == {"sort": 0, "pallas_call": 0}, counts


@pytest.mark.parametrize("name", FUSED_BACKENDS)
def test_routed_fused_stack_lookup_budget(name):
    """The acceptance budget: route (capped) + fused stack lookup lowers to
    exactly 1 pallas_call and the fused op's own sort TOTAL (the
    sorted-slab kernels' bucket sort; the windowed linear kernels need
    none) — the router adds no sort."""
    be = backend.get(name)
    budget = {"sort": 0 if name == "linear" else 1, "pallas_call": 1}
    s, q = 4, 64
    st = dhash.make_stack(s, name, 256, chunk=64, seed=0, fused=True)
    keys = jnp.arange(1, q + 1, dtype=jnp.int32)
    owner = keys % s
    cap = dd.route_cap(2.0, q, s)

    def routed_fast(st, k, o):
        rt = dd._route(k, o, s, cap)
        f, v = jax.vmap(lambda d, kk: be.lookup_fused(d.old, kk))(st, rt.send)
        return dd._unroute(f & rt.smask, rt, fill=False)

    counts = _count_primitives(jax.make_jaxpr(routed_fast)(st, keys, owner),
                               ("sort", "pallas_call"))
    assert counts == budget, (name, counts)

    def routed_ordered(st, k, o):
        rt = dd._route(k, o, s, cap)
        f, v = jax.vmap(lambda d, kk: be.ordered_lookup_fused(
            d.old, d.new, d.hazard_key, d.hazard_val, d.hazard_live, kk,
            nres_cap=d.nres_cap))(st, rt.send)
        return dd._unroute(f & rt.smask, rt, fill=False)

    counts = _count_primitives(jax.make_jaxpr(routed_ordered)(st, keys, owner),
                               ("sort", "pallas_call"))
    assert counts == budget, (name, counts)


def test_capped_stack_lookup_exact_on_kept_keys():
    """End-to-end at the stack level (no mesh): capped routed lookups agree
    key-for-key with per-table lookups; spilled keys come back not-found
    (and are exactly the ones ``overflow`` counts)."""
    rng = np.random.default_rng(9)
    s, q = 4, 64
    st = dhash.make_stack(s, "linear", 256, chunk=64, seed=1)
    keys = jnp.asarray(rng.choice(100_000, q, replace=False).astype(np.int32))
    owner = jnp.asarray(rng.integers(0, s, q).astype(np.int32))
    # populate via a FULL-width route (no drops), then read back capped
    full = dd._route(keys, owner, s)
    st, ok = dhash.stack_insert(st, full.send, full.send * 5, full.smask)
    assert bool(np.asarray(dd._unroute(ok, full, fill=False)).all())
    rt = dd._route(keys, owner, s, dd.route_cap(1.0, q, s))
    f, v = dhash.stack_lookup(st, rt.send, rt.smask)
    found = np.asarray(dd._unroute(f, rt, fill=False).astype(bool))
    vals = np.asarray(dd._unroute(v, rt, fill=0))
    kept = np.asarray(rt.kept)
    np.testing.assert_array_equal(found, kept)    # kept ⇒ hit, spilled ⇒ miss
    np.testing.assert_array_equal(vals[kept], np.asarray(keys)[kept] * 5)
    assert int(rt.overflow.sum()) == int((~kept).sum())


# -- two-level spill slab ----------------------------------------------------


def _ref_slab_route(keys, owner, nshards, cap, spill_cap):
    """Stable two-level reference in plain NumPy: primary columns by
    owner rank, slab columns shared across owners by global spill rank
    (batch order), exact per-owner drop counts past the slab."""
    keys, owner = np.asarray(keys), np.asarray(owner)
    send = np.zeros((nshards, cap + spill_cap), keys.dtype)
    smask = np.zeros((nshards, cap + spill_cap), bool)
    served = np.zeros(keys.shape[0], bool)
    slab_owner = np.full(spill_cap, -1, np.int64)
    fill = np.zeros(nshards, np.int64)
    dropped = np.zeros(nshards, np.int64)
    nspill = 0
    for i in range(keys.shape[0]):
        o = int(owner[i])
        r = fill[o]
        fill[o] += 1
        if r < cap:
            send[o, r] = keys[i]
            smask[o, r] = True
            served[i] = True
        else:
            j = nspill
            nspill += 1
            if j < spill_cap:
                send[o, cap + j] = keys[i]
                smask[o, cap + j] = True
                slab_owner[j] = o
                served[i] = True
            else:
                dropped[o] += 1
    return send, smask, served, slab_owner, dropped


def _owner_batch(skew, rng, q, s):
    if skew == "uniform":
        owner = rng.integers(0, s, q)
    elif skew == "zipfish":
        owner = np.minimum(rng.zipf(1.5, q) - 1, s - 1)
    elif skew == "one_owner":
        owner = np.full(q, s - 1)
    else:                                          # all_spill: cap=1 regime
        owner = np.repeat(np.arange(s), q // s)
    return jnp.asarray(owner.astype(np.int32))


@pytest.mark.parametrize("skew", ["uniform", "zipfish", "one_owner",
                                  "all_spill"])
def test_slab_route_matches_reference(skew):
    rng = np.random.default_rng(17)
    q, s = 96, 8
    keys = jnp.asarray(rng.choice(10_000, q, replace=False).astype(np.int32))
    owner = _owner_batch(skew, rng, q, s)
    cap = 1 if skew == "all_spill" else dd.route_cap(1.0, q, s)
    for spill_cap in (dd.route_spill_cap(q, cap),          # overflow-proof
                      dd.route_spill_cap(q, cap, 0.1),     # compact: drops
                      0):                                   # slab disabled
        rt = dd._route(keys, owner, s, cap, spill_cap)
        send, smask, served, slab_owner, dropped = _ref_slab_route(
            np.asarray(keys), np.asarray(owner), s, cap, spill_cap)
        assert rt.send.shape == (s, cap + spill_cap)
        np.testing.assert_array_equal(np.asarray(rt.send), send)
        np.testing.assert_array_equal(np.asarray(rt.smask), smask)
        np.testing.assert_array_equal(np.asarray(rt.served), served)
        np.testing.assert_array_equal(np.asarray(rt.slab_owner), slab_owner)
        np.testing.assert_array_equal(np.asarray(rt.dropped), dropped)
        # exact accounting closes: every key is served, spilled-but-slabbed,
        # or dropped — and overflow still counts ALL spill (slab + dropped)
        hist = np.bincount(np.asarray(owner), minlength=s)
        np.testing.assert_array_equal(np.asarray(rt.overflow),
                                      np.maximum(hist - cap, 0))
        assert int(rt.served.sum()) + int(rt.dropped.sum()) == q
        assert int(rt.dropped.sum()) == max(
            int(rt.overflow.sum()) - spill_cap, 0)
    # the overflow-proof slab NEVER drops, under any skew
    rt = dd._route(keys, owner, s, cap, dd.route_spill_cap(q, cap))
    assert bool(np.asarray(rt.served).all())
    assert int(rt.dropped.sum()) == 0


@pytest.mark.parametrize("name", FUSED_BACKENDS)
@pytest.mark.parametrize("skew", ["uniform", "zipfish", "one_owner",
                                  "all_spill"])
def test_slab_route_bit_identical_to_full_width(name, skew):
    """The acceptance differential: with the overflow-proof slab, a capped
    route serves EVERY key — lookups and inserts through the slab layout
    return bit-identical results to full-width routing, on every fused
    backend, under every skew."""
    rng = np.random.default_rng(23)
    q, s = 96, 8
    st0 = dhash.make_stack(s, name, 512, chunk=64, seed=3, fused=True)
    keys = jnp.asarray(rng.choice(100_000, q, replace=False).astype(np.int32))
    owner = _owner_batch(skew, rng, q, s)
    cap = 1 if skew == "all_spill" else dd.route_cap(1.0, q, s)
    spill_cap = dd.route_spill_cap(q, cap)
    ones = jnp.ones(q, bool)

    # insert differential: slab-routed insert vs full-width insert
    full = dd._route(keys, owner, s)
    rt = dd._route(keys, owner, s, cap, spill_cap)
    assert int(rt.dropped.sum()) == 0
    st_f, ok_f = dhash.stack_insert(st0, full.send, full.send * 5, full.smask)
    st_r, ok_r = dhash.stack_insert(st0, rt.send, rt.send * 5,
                                    dd._route_payload(ones, rt) & rt.smask)
    np.testing.assert_array_equal(
        np.asarray(dd._unroute(ok_r, rt, fill=False)),
        np.asarray(dd._unroute(ok_f, full, fill=False)))

    # lookup differential on BOTH resulting tables
    for st in (st_f, st_r):
        f_f, v_f = dhash.stack_lookup(st, full.send, full.smask)
        f_r, v_r = dhash.stack_lookup(st, rt.send, rt.smask)
        np.testing.assert_array_equal(
            np.asarray(dd._unroute(f_r, rt, fill=False)),
            np.asarray(dd._unroute(f_f, full, fill=False)))
        np.testing.assert_array_equal(
            np.asarray(dd._unroute(v_r, rt, fill=0)),
            np.asarray(dd._unroute(v_f, full, fill=0)))
        found = np.asarray(dd._unroute(f_r, rt, fill=False).astype(bool))
        assert found.all(), (name, skew)          # every key served and hit


def test_slab_compact_drop_accounting_end_to_end():
    """A compact slab that runs out: dropped keys come back not-found with
    the unmistakable fill, and ``dropped`` counts them exactly per owner."""
    rng = np.random.default_rng(29)
    q, s = 64, 4
    st = dhash.make_stack(s, "linear", 256, chunk=64, seed=5)
    keys = jnp.asarray(rng.choice(100_000, q, replace=False).astype(np.int32))
    owner = jnp.zeros(q, jnp.int32)               # 100% skew
    full = dd._route(keys, owner, s)
    st, _ = dhash.stack_insert(st, full.send, full.send * 7, full.smask)
    cap = dd.route_cap(1.0, q, s)                 # 16: 48 keys spill
    spill_cap = dd.route_spill_cap(q, cap, 0.25)  # 16: 32 keys dropped
    rt = dd._route(keys, owner, s, cap, spill_cap)
    assert int(rt.dropped.sum()) == 32 and int(rt.dropped[0]) == 32
    f, v = dhash.stack_lookup(st, rt.send, rt.smask)
    found = np.asarray(dd._unroute(f, rt, fill=False).astype(bool))
    served = np.asarray(rt.served)
    assert served.sum() == q - 32
    np.testing.assert_array_equal(found, served)  # served ⇒ hit, dropped ⇒ miss
    vals = np.asarray(dd._unroute(v, rt, fill=0))
    np.testing.assert_array_equal(vals[served], np.asarray(keys)[served] * 7)
    assert (vals[~served] == 0).all()


@pytest.mark.parametrize("name", FUSED_BACKENDS)
def test_adversarial_slab_routed_budget(name):
    """The acceptance pin: a 100%-skew adversarial batch routed through the
    spill-slab layout lowers to exactly 1 pallas_call and the fused op's
    own sort TOTAL (none for the windowed linear kernels) — no cond-gated
    second pass anywhere, the spilling batch costs the same op as a
    balanced one."""
    be = backend.get(name)
    s, q = 8, 64
    st = dhash.make_stack(s, name, 256, chunk=64, seed=0, fused=True)
    keys = jnp.arange(1, q + 1, dtype=jnp.int32)
    owner = jnp.full(q, 3, jnp.int32)             # every key one owner
    cap = dd.route_cap(2.0, q, s)
    spill_cap = dd.route_spill_cap(q, cap)        # overflow-proof

    def routed(st, k, o):
        rt = dd._route(k, o, s, cap, spill_cap)
        f, v = jax.vmap(lambda d, kk: be.lookup_fused(d.old, kk))(st, rt.send)
        return dd._unroute(f & rt.smask, rt, fill=False), rt.dropped

    counts = _count_primitives(jax.make_jaxpr(routed)(st, keys, owner),
                               ("sort", "pallas_call", "cond"))
    assert counts == {"sort": 0 if name == "linear" else 1,
                      "pallas_call": 1, "cond": 0}, (name, counts)


def test_grid_owner_flat_ids():
    keys = jnp.arange(1, 33, dtype=jnp.int32)
    tenant = keys % 3
    from repro.core import hashing
    hfn = hashing.fresh("tabulation", 7)
    own = dd.grid_owner(keys, tenant, 4, 3, hfn)
    shard = dd.shard_of(keys, 4, hfn)
    np.testing.assert_array_equal(np.asarray(own),
                                  np.asarray(shard) * 3 + np.asarray(tenant))
    assert int(own.min()) >= 0 and int(own.max()) < 12
