"""Growth-factor rebuild parity: the fused rebuild-epoch ops vs the jnp
oracle at 1x/4x/16x new-table growth (the two-level tile-map acceptance).

The shared query sort is keyed on the OLD table's start slots, so a grown
new table scatters each tile's new-table windows across many slabs; the
two-level tile map (per-tile resident blocks, ``ops.NRES_CAP`` of them) of the
sorted-slab backends, and the per-query windows of the linear kernels, must
keep the ordered check exact AND fused at every growth factor — including
non-power-of-two capacities and non-tile-multiple batches, where the edge
padding and block clipping are most exposed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import count_primitives
from repro.core import buckets, dhash, hashing
from repro.kernels import ops, ref

GROWTHS = (1, 4, 16)


def _linear_pair(c_old, c_new, n_old, n_new, seed, max_probes=32):
    rng = np.random.default_rng(seed)
    told = buckets.linear_make(c_old, hashing.fresh("mix32", seed),
                               max_probes=max_probes)
    k1 = jnp.asarray(rng.choice(10_000_000, n_old, replace=False)
                     .astype(np.int32))
    told, _ = jax.jit(buckets.linear_insert)(told, k1, k1 * 3,
                                             jnp.ones(k1.shape, bool))
    tnew = buckets.linear_make(c_new, hashing.fresh("mix32", seed + 1),
                               max_probes=max_probes)
    k2 = jnp.asarray(rng.choice(np.arange(30_000_000, 40_000_000), n_new,
                                replace=False).astype(np.int32))
    tnew, _ = jax.jit(buckets.linear_insert)(tnew, k2, k2 * 9,
                                             jnp.ones(k2.shape, bool))
    hk = jnp.asarray(rng.choice(np.arange(20_000_000, 21_000_000), 64,
                                replace=False).astype(np.int32))
    hv = hk * 7
    hl = jnp.asarray(rng.random(64) < 0.7)
    return told, tnew, k1, k2, hk, hv, hl, rng


@pytest.mark.parametrize("growth", GROWTHS)
def test_linear_growth_lookup_parity(growth):
    """Fused ordered lookup == oracle with a grown, NON-power-of-two new
    table and a non-tile-multiple batch; budget stays 1 pallas_call, no
    sort."""
    c_old = 3000                                   # non-power-of-two
    c_new = c_old * growth + 37                    # non-pow2, non-multiple
    told, tnew, k1, k2, hk, hv, hl, rng = _linear_pair(
        c_old, c_new, 1_500, 1_500, seed=growth)
    qs = jnp.concatenate([k1[:700], k2[:700], hk,
                          jnp.asarray(rng.integers(2**30, 2**31 - 1, 572)
                                      .astype(np.int32))])  # 2033 queries
    h0o = hashing.bucket_of(told.hfn, qs, c_old)
    h0n = hashing.bucket_of(tnew.hfn, qs, c_new)
    args = ((told.key, told.val, told.state), (tnew.key, tnew.val, tnew.state),
            hk, hv, hl, h0o, h0n, qs)
    f_r, v_r = ref.ordered_lookup_ref(*args, max_probes=32)
    f_k, v_k = ops.ordered_lookup_fused(*args, max_probes=32)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))

    jx = jax.make_jaxpr(
        lambda *a: ops.ordered_lookup_fused(*a, max_probes=32))(*args)
    assert count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 0, "pallas_call": 1}


@pytest.mark.parametrize("growth", GROWTHS)
def test_linear_growth_delete_parity(growth):
    """Fused ordered delete == the staged jnp ordered delete (old tombstone /
    hazard kill / new tombstone) under growth."""
    c_old = 2_900
    c_new = c_old * growth + 51
    told, tnew, k1, k2, hk, hv, hl, rng = _linear_pair(
        c_old, c_new, 1_200, 1_200, seed=10 + growth)
    dels = jnp.concatenate([k1[::4], k2[::4], hk[:24],
                            jnp.asarray(rng.integers(2**29, 2**30, 101)
                                        .astype(np.int32))])
    win = buckets.batch_winners(dels, jnp.ones(dels.shape, bool))
    h0o = hashing.bucket_of(told.hfn, dels, c_old)
    h0n = hashing.bucket_of(tnew.hfn, dels, c_new)
    old_t = (told.key, told.val, told.state)
    new_t = (tnew.key, tnew.val, tnew.state)
    os_k, ns_k, hl_k, ok_k = ops.ordered_delete_fused(
        old_t, new_t, hk, hv, hl, h0o, h0n, dels, win, max_probes=32)

    # staged oracle: old -> hazard -> new
    os_r, ok_o = ref.probe_delete_ref(told.key, told.val, told.state,
                                      h0o, dels, win, 32)
    pend = win & ~ok_o
    eq = (dels[:, None] == hk[None, :]) & hl[None, :]
    hz_hit = eq.any(-1) & pend
    kill = jnp.zeros_like(hl).at[
        jnp.where(hz_hit, jnp.argmax(eq, axis=-1), 64)].set(True, mode="drop")
    ns_r, ok_n = ref.probe_delete_ref(tnew.key, tnew.val, tnew.state,
                                      h0n, dels, pend & ~hz_hit, 32)
    np.testing.assert_array_equal(np.asarray(ok_k),
                                  np.asarray(ok_o | hz_hit | ok_n))
    np.testing.assert_array_equal(np.asarray(os_k), np.asarray(os_r))
    np.testing.assert_array_equal(np.asarray(ns_k), np.asarray(ns_r))
    np.testing.assert_array_equal(np.asarray(hl_k), np.asarray(hl & ~kill))


def test_linear_16x_escape_rate_under_5pct():
    """At 16x growth the fused probe resolves every rebuild-epoch query
    in-kernel: the linear ordered lookup has no fallback branch (each query
    reads its own new-table window), and it matches the oracle."""
    c_old = 4096
    told, tnew, k1, k2, hk, hv, hl, rng = _linear_pair(
        c_old, c_old * 16, 3_000, 2_000, seed=3)
    qs = jnp.concatenate([k1[:1000], k2[:1000], hk,
                          jnp.asarray(rng.integers(2**30, 2**31 - 1, 2033)
                                      .astype(np.int32))])
    h0o = hashing.bucket_of(told.hfn, qs, c_old)
    h0n = hashing.bucket_of(tnew.hfn, qs, c_old * 16)
    args = ((told.key, told.val, told.state),
            (tnew.key, tnew.val, tnew.state), hk, hv, hl, h0o, h0n, qs)
    jx = jax.make_jaxpr(
        lambda *a: ops.ordered_lookup_fused(*a, max_probes=32))(*args)
    assert count_primitives(jx, ("cond",)) == {"cond": 0}
    f_r, v_r = ref.ordered_lookup_ref(*args, max_probes=32)
    f_k, v_k = ops.ordered_lookup_fused(*args, max_probes=32)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))


def _tc_pair(nb_old, nb_new, n_old, n_new, seed, width=8):
    rng = np.random.default_rng(seed)
    to = buckets.twochoice_make(nb_old, hashing.fresh("mix32", seed),
                                hashing.fresh("mix32", seed + 1), width=width)
    k1 = jnp.asarray(rng.choice(1_000_000, n_old, replace=False)
                     .astype(np.int32))
    to, _ = jax.jit(buckets.twochoice_insert)(to, k1, k1 * 5,
                                              jnp.ones(k1.shape, bool))
    tn = buckets.twochoice_make(nb_new, hashing.fresh("mix32", seed + 2),
                                hashing.fresh("mix32", seed + 3), width=width)
    k2 = jnp.asarray(rng.choice(np.arange(2_000_000, 3_000_000), n_new,
                                replace=False).astype(np.int32))
    tn, _ = jax.jit(buckets.twochoice_insert)(tn, k2, k2 * 9,
                                              jnp.ones(k2.shape, bool))
    hk = jnp.asarray(rng.choice(np.arange(5_000_000, 6_000_000), 64,
                                replace=False).astype(np.int32))
    hv = hk * 7
    hl = jnp.asarray(rng.random(64) < 0.7)
    return to, tn, k1, k2, hk, hv, hl, rng


def _tc_ordered_oracle_lookup(to, tn, hk, hv, hl, rows, qs):
    (bao, bbo), (ban, bbn) = rows
    fa, va, _ = ref.tc_row_lookup_ref(to.key, to.val, to.state, bao, qs)
    fb, vb, _ = ref.tc_row_lookup_ref(to.key, to.val, to.state, bbo, qs)
    fo, vo = fa | fb, jnp.where(fa, va, vb)
    eq = (qs[:, None] == hk[None, :]) & hl[None, :]
    fh = eq.any(-1)
    vh = jnp.take(hv, jnp.argmax(eq, axis=-1))
    fna, vna, _ = ref.tc_row_lookup_ref(tn.key, tn.val, tn.state, ban, qs)
    fnb, vnb, _ = ref.tc_row_lookup_ref(tn.key, tn.val, tn.state, bbn, qs)
    fnw, vnw = fna | fnb, jnp.where(fna, vna, vnb)
    found = fo | fh | fnw
    val = jnp.where(fo, vo, jnp.where(fh, vh, jnp.where(fnw, vnw, 0)))
    return found, jnp.where(found, val, 0)


@pytest.mark.parametrize("growth", GROWTHS)
def test_twochoice_growth_lookup_parity(growth):
    """Single-pass fused twochoice ordered lookup == the staged jnp oracle
    at growth, non-pow2 bucket counts, odd batch size; budget 1 sort +
    1 pallas_call."""
    nb_old = 509                                   # non-power-of-two rows
    nb_new = nb_old * growth + 3
    to, tn, k1, k2, hk, hv, hl, rng = _tc_pair(nb_old, nb_new, 1_200, 1_200,
                                               seed=20 + growth)
    qs = jnp.concatenate([k1[:500], k2[:500], hk,
                          jnp.asarray(rng.integers(2**30, 2**31 - 1, 401)
                                      .astype(np.int32))])
    rows = (buckets._tc_rows(to, qs), buckets._tc_rows(tn, qs))
    args = ((to.key, to.val, to.state), (tn.key, tn.val, tn.state),
            hk, hv, hl, *rows[0], *rows[1], qs)
    f_k, v_k = ops.twochoice_ordered_lookup(*args)
    f_r, v_r = _tc_ordered_oracle_lookup(to, tn, hk, hv, hl, rows, qs)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    fm = np.asarray(f_r)
    np.testing.assert_array_equal(np.asarray(v_k)[fm], np.asarray(v_r)[fm])

    jx = jax.make_jaxpr(lambda *a: ops.twochoice_ordered_lookup(*a))(*args)
    assert count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}


@pytest.mark.parametrize("growth", GROWTHS)
def test_twochoice_growth_delete_parity(growth):
    """Single-pass fused twochoice ordered delete == the staged jnp ordered
    delete on states, hazard kills, and ok flags, under growth."""
    nb_old = 487
    nb_new = nb_old * growth + 5
    to, tn, k1, k2, hk, hv, hl, rng = _tc_pair(nb_old, nb_new, 1_000, 1_000,
                                               seed=30 + growth)
    dels = jnp.concatenate([k1[::5], k2[::5], hk[:20],
                            jnp.asarray(rng.integers(2**29, 2**30, 77)
                                        .astype(np.int32))])
    win = buckets.batch_winners(dels, jnp.ones(dels.shape, bool))
    rows = (buckets._tc_rows(to, dels), buckets._tc_rows(tn, dels))
    args = ((to.key, to.val, to.state), (tn.key, tn.val, tn.state),
            hk, hv, hl, *rows[0], *rows[1], dels, win)
    os_k, ns_k, hl_k, ok_k = ops.twochoice_ordered_delete(*args)

    os_r, ok_o = ref.tc_delete_ref(to.key, to.val, to.state,
                                   *rows[0], dels, win)
    pend = win & ~ok_o
    eq = (dels[:, None] == hk[None, :]) & hl[None, :]
    hz_hit = eq.any(-1) & pend
    kill = jnp.zeros_like(hl).at[
        jnp.where(hz_hit, jnp.argmax(eq, axis=-1), 64)].set(True, mode="drop")
    ns_r, ok_n = ref.tc_delete_ref(tn.key, tn.val, tn.state,
                                   *rows[1], dels, pend & ~hz_hit)
    np.testing.assert_array_equal(np.asarray(ok_k),
                                  np.asarray(ok_o | hz_hit | ok_n))
    np.testing.assert_array_equal(np.asarray(os_k), np.asarray(os_r))
    np.testing.assert_array_equal(np.asarray(ns_k), np.asarray(ns_r))
    np.testing.assert_array_equal(np.asarray(hl_k), np.asarray(hl & ~kill))

    jx = jax.make_jaxpr(lambda *a: ops.twochoice_ordered_delete(*a))(*args)
    assert count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}


@pytest.mark.parametrize("backend", ["linear", "twochoice"])
def test_dhash_grown_rebuild_interleaved(backend):
    """End-to-end: a fused DHashState rebuilding into a 4x GROWN user-
    supplied new table, with deletes and lookups interleaved mid-rebuild,
    matches its unfused twin on every observable."""
    rng = np.random.default_rng(42)
    mk = lambda fused: dhash.make(backend, capacity=600, chunk=128, seed=5,  # noqa: E731
                                  fused=fused)
    d_j, d_k = mk(False), mk(True)
    keys = jnp.asarray(rng.choice(100_000, 473, replace=False)
                       .astype(np.int32))
    ins = jax.jit(dhash.insert)
    d_j, _ = ins(d_j, keys, keys * 2)
    d_k, _ = ins(d_k, keys, keys * 2)

    if backend == "linear":
        grown = buckets.linear_make(buckets.capacity_of(d_j.old) * 4,
                                    hashing.fresh("mix32", 77),
                                    max_probes=d_j.old.max_probes)
    else:
        grown = buckets.twochoice_make(d_j.old.nbuckets * 4,
                                       hashing.fresh("mix32", 77),
                                       hashing.fresh("mix32", 78),
                                       width=d_j.old.width)
    d_j = dhash.rebuild_start(d_j, jax.tree_util.tree_map(jnp.copy, grown))
    d_k = dhash.rebuild_start(d_k, grown)
    step = jax.jit(dhash.rebuild_step)
    dl = jax.jit(dhash.delete)
    look = jax.jit(dhash.lookup)
    i = 0
    while bool(jax.device_get(d_k.rebuilding)) and i < 64:
        d_j, d_k = step(d_j), step(d_k)
        dels = keys[i::16][:5]
        d_j, ok_j = dl(d_j, jnp.asarray(dels))
        d_k, ok_k = dl(d_k, jnp.asarray(dels))
        np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_j))
        f_j, v_j = look(d_j, keys[:101])
        f_k, v_k = look(d_k, keys[:101])
        np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_j))
        fm = np.asarray(f_j)
        np.testing.assert_array_equal(np.asarray(v_k)[fm],
                                      np.asarray(v_j)[fm])
        if bool(jax.device_get(dhash.rebuild_done(d_k))):
            d_j, d_k = dhash.rebuild_finish(d_j), dhash.rebuild_finish(d_k)
        i += 1
    assert int(d_k.epoch) == 1, "grown rebuild did not complete"
    assert int(dhash.count_items(d_j)) == int(dhash.count_items(d_k))
