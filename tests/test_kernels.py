"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle,
exactly as specified — assert_allclose per cell (exact for int compare)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import count_primitives as _count_primitives
from repro.core import buckets, dhash, hashing
from repro.kernels import ops, ref


def _table(capacity, n_items, seed, max_probes=32, deletes=0):
    rng = np.random.default_rng(seed)
    t = buckets.linear_make(capacity, hashing.fresh("mix32", seed),
                            max_probes=max_probes)
    keys = jnp.asarray(rng.choice(10_000_000, size=n_items, replace=False)
                       .astype(np.int32))
    t, ok = jax.jit(buckets.linear_insert)(t, keys, keys * 3,
                                           jnp.ones(keys.shape, bool))
    if deletes:
        t, _ = jax.jit(buckets.linear_delete)(t, keys[:deletes],
                                              jnp.ones(deletes, bool))
    return t, keys, np.asarray(ok)


@pytest.mark.parametrize("capacity,n_items,n_queries", [
    (1 << 10, 500, 333),          # small, non-tile-aligned query count
    (1 << 14, 9_000, 4_096),      # multi-tile
    (1 << 15, 20_000, 10_001),    # odd query count, several slabs
])
def test_probe_lookup_matches_ref(capacity, n_items, n_queries):
    t, keys, ok = _table(capacity, n_items, seed=capacity % 97)
    rng = np.random.default_rng(1)
    qs = jnp.concatenate([
        keys[: min(n_items, n_queries // 2)],
        jnp.asarray(rng.integers(10_000_000, 2**31 - 1, n_queries)
                    .astype(np.int32))])[:n_queries]
    h0 = hashing.bucket_of(t.hfn, qs, t.capacity)
    f_ref, v_ref = ref.probe_lookup_ref(t.key, t.val, t.state, h0, qs, 32)
    f_k, v_k = ops.probe_lookup(t.key, t.val, t.state, h0, qs, max_probes=32)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref))


def test_probe_lookup_with_tombstones():
    t, keys, _ = _table(1 << 13, 4_000, seed=3, deletes=1_000)
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    f_ref, v_ref = ref.probe_lookup_ref(t.key, t.val, t.state, h0, keys, 64)
    f_k, v_k = ops.probe_lookup(t.key, t.val, t.state, h0, keys, max_probes=64)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    assert int(f_k.sum()) == 3_000


def test_probe_lookup_adversarial_skew():
    """All queries hash into one region (the paper's collision attack):
    the slab fallback path must stay exact."""
    t = buckets.linear_make(1 << 14, hashing.fresh("mix32", 0), max_probes=64)
    # force a dense contiguous run by inserting colliding-by-construction keys
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.choice(1_000_000, 3000, replace=False).astype(np.int32))
    t, _ = jax.jit(buckets.linear_insert)(t, keys, keys, jnp.ones(3000, bool))
    qs = jnp.tile(keys[:128], 32)                     # heavy duplicate queries
    h0 = hashing.bucket_of(t.hfn, qs, t.capacity)
    f_ref, v_ref = ref.probe_lookup_ref(t.key, t.val, t.state, h0, qs, 64)
    f_k, v_k = ops.probe_lookup(t.key, t.val, t.state, h0, qs, max_probes=64)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref))


def _ordered_args(n_old=1_500, n_new=1_200, n_q=4_096, hazard=64, seed=7):
    rng = np.random.default_rng(seed)
    told, keys, _ = _table(1 << 12, n_old, seed=11)
    tnew, keys2, _ = _table(1 << 12, n_new, seed=12)
    hk = jnp.asarray(rng.choice(10_000_000, hazard, replace=False).astype(np.int32))
    hv = hk * 7
    hl = jnp.asarray(rng.random(hazard) < 0.7)
    qs = jnp.concatenate([keys, keys2, hk,
                          jnp.asarray(rng.integers(2**30, 2**31 - 1, n_q)
                                      .astype(np.int32))])[:n_q]
    h0_old = hashing.bucket_of(told.hfn, qs, told.capacity)
    h0_new = hashing.bucket_of(tnew.hfn, qs, tnew.capacity)
    return ((told.key, told.val, told.state), (tnew.key, tnew.val, tnew.state),
            hk, hv, hl, h0_old, h0_new, qs)


def test_fused_rebuild_lookup_single_sort_single_pallas_call():
    """Acceptance: during an active rebuild the fused lookup path executes
    ONE pallas_call per batch and no sort (the windowed kernels gather each
    query's window instead of sorting the batch into slabs); the unfused
    path pays at least two kernel passes (old pass + new pass)."""
    args = _ordered_args(n_q=4_096)
    fused = jax.make_jaxpr(
        lambda *a: ops.ordered_lookup_fused(*a, max_probes=32))(*args)
    unfused = jax.make_jaxpr(
        lambda *a: ops.ordered_lookup(*a, max_probes=32))(*args)
    nf = _count_primitives(fused, ("sort", "pallas_call"))
    nu = _count_primitives(unfused, ("sort", "pallas_call"))
    assert nf == {"sort": 0, "pallas_call": 1}, nf
    assert nu["pallas_call"] >= 2, nu
    # pass-count reduction is the interpret-mode proxy for the >=1.5x
    # rebuild-epoch throughput criterion (see bench_rebuild --fused)
    passes_u = nu["sort"] + nu["pallas_call"]
    passes_f = nf["sort"] + nf["pallas_call"]
    assert passes_u / passes_f >= 1.5


def test_probe2_matches_ref():
    """Fused two-table+hazard kernel == ordered oracle (multi-tile batch with
    duplicates and hazard hits)."""
    args = _ordered_args(n_q=4_096)
    f_ref, v_ref = ref.ordered_lookup_ref(*args, max_probes=32)
    f_k, v_k = ops.ordered_lookup_fused(*args, max_probes=32)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref))


def test_probe2_skew_forced_fallback():
    """A large new table makes per-tile new-slab windows miss (h0_new is
    scattered while the shared sort is keyed on h0_old): complete=False
    queries must be recovered exactly by the gated fallback; duplicate query
    keys ride along."""
    rng = np.random.default_rng(3)
    told, keys, _ = _table(1 << 12, 1_000, seed=21)
    tnew = buckets.linear_make(1 << 15, hashing.fresh("mix32", 22), max_probes=32)
    k2 = jnp.asarray(rng.choice(10_000_000, 5_000, replace=False).astype(np.int32))
    tnew, _ = jax.jit(buckets.linear_insert)(tnew, k2, k2 * 9,
                                             jnp.ones(k2.shape, bool))
    hz = jnp.zeros(32, jnp.int32)
    qs = jnp.concatenate([k2[:2000], jnp.tile(k2[:128], 8), keys])
    h0_old = hashing.bucket_of(told.hfn, qs, told.capacity)
    h0_new = hashing.bucket_of(tnew.hfn, qs, tnew.capacity)
    args = ((told.key, told.val, told.state), (tnew.key, tnew.val, tnew.state),
            hz, hz, jnp.zeros(32, bool), h0_old, h0_new, qs)
    f_ref, v_ref = ref.ordered_lookup_ref(*args, max_probes=32)
    f_k, v_k = ops.ordered_lookup_fused(*args, max_probes=32)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref))


def test_probe_insert_matches_oracle_low_load():
    """Claim kernel == insert oracle at low load: identical ok flags, every
    inserted key readable with its value, live-count conserved."""
    rng = np.random.default_rng(5)
    t = buckets.linear_make(1 << 13, hashing.fresh("mix32", 5), max_probes=32)
    keys = jnp.asarray(rng.choice(1_000_000, 3_000, replace=False).astype(np.int32))
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    mask = jnp.ones(keys.shape, bool)
    tk, tv, ts, ok = ops.probe_insert(t.key, t.val, t.state, h0, keys,
                                      keys * 5, mask, max_probes=32)
    _, _, ts_ref, ok_ref = ref.probe_insert_ref(t.key, t.val, t.state, h0,
                                                keys, keys * 5, mask, 32)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok_ref))
    assert bool(ok.all())
    assert int((ts == 1).sum()) == int((ts_ref == 1).sum()) == 3_000
    f, v = ref.probe_lookup_ref(tk, tv, ts, h0, keys, 32)
    assert bool(f.all()) and bool((v == keys * 5).all())


def test_probe_insert_duplicates_and_existing():
    """buckets.linear_insert_fused (winner dedup + kernel) must agree with
    the jnp linear_insert on every observable: ok counts per key, final
    membership, values."""
    rng = np.random.default_rng(9)
    base = jnp.asarray(rng.choice(1_000_000, 500, replace=False).astype(np.int32))
    t0 = buckets.linear_make(1 << 12, hashing.fresh("mix32", 1), max_probes=32)
    t0, _ = jax.jit(buckets.linear_insert)(t0, base, base * 2,
                                           jnp.ones(base.shape, bool))
    # batch: duplicates of new keys, re-inserts of existing keys, masked-out
    fresh = jnp.asarray(rng.choice(np.arange(2_000_000, 3_000_000), 400,
                                   replace=False).astype(np.int32))
    batch = jnp.concatenate([fresh, fresh[:200], base[:100]])
    vals = batch * 3
    mask = jnp.ones(batch.shape, bool).at[-50:].set(False)
    t_j, ok_j = jax.jit(buckets.linear_insert)(t0, batch, vals, mask)
    t_k, ok_k = jax.jit(buckets.linear_insert_fused)(t0, batch, vals, mask)
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_j))
    assert int(buckets.linear_count_live(t_k)) == int(buckets.linear_count_live(t_j))
    probe = jnp.concatenate([base, fresh])
    f_j, v_j, _ = buckets.linear_lookup(t_j, probe)
    f_k, v_k, _ = buckets.linear_lookup(t_k, probe)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_j))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_j))


def test_probe_insert_full_table_pressure():
    """Near-capacity insert with a short probe bound: successful claims are
    readable, failures genuinely exhausted their windows, no slot double-
    claimed (live count == ok count)."""
    rng = np.random.default_rng(4)
    t = buckets.linear_make(1 << 10, hashing.fresh("mix32", 5), max_probes=16)
    keys = jnp.asarray(rng.choice(1_000_000, 1_200, replace=False).astype(np.int32))
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    mask = jnp.ones(keys.shape, bool)
    tk, tv, ts, ok = ops.probe_insert(t.key, t.val, t.state, h0, keys, keys,
                                      mask, max_probes=16)
    _, _, _, ok_ref = ref.probe_insert_ref(t.key, t.val, t.state, h0, keys,
                                           keys, mask, 16)
    # settling by probe distance then batch index is the oracle's order
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok_ref))
    assert int((ts == 1).sum()) == int(ok.sum())       # no double-claims
    f, v = ref.probe_lookup_ref(tk, tv, ts, h0, keys, 16)
    assert bool(f[ok].all()) and bool((v[ok] == keys[ok]).all())
    assert not bool(f[~ok].any())                       # failures not inserted


@pytest.mark.parametrize("max_probes", [16, 128])
@pytest.mark.parametrize("fused", [False, True])
def test_linear_insert_tables_match_oracle(max_probes, fused):
    """Both linear inserts build the step-by-step oracle's tables slot for
    slot: a 1024-slot table filled to full load through tombstones, with
    contested slots, occupied runs far past 64 slots and (at the bound 16)
    inserts refused for a full probe range."""
    rng = np.random.default_rng(11)
    c = 1 << 10
    t = buckets.linear_make(c, hashing.fresh("mix32", 3), max_probes=max_probes)
    want = (t.key, t.val, t.state)
    keys = jnp.asarray(rng.choice(10_000_000, 1_400, replace=False)
                       .astype(np.int32))
    insert = jax.jit(buckets.linear_insert_fused if fused
                     else buckets.linear_insert)
    for lo, hi in ((0, 700), (700, 1_100), (1_100, 1_400)):
        b = keys[lo:hi]
        mask = jnp.ones(b.shape, bool)
        t, ok = insert(t, b, b * 3, mask)
        h0 = hashing.bucket_of(t.hfn, b, c)
        *want, ok_ref = ref.probe_insert_ref(
            *want, h0, b, b * 3, buckets.batch_winners(b, mask), max_probes)
        np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok_ref))
        for got, exp in zip((t.key, t.val, t.state), want):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
        if lo == 0:                                    # tombstones to reuse
            d, dm = keys[:150], jnp.ones(150, bool)
            t, _ = jax.jit(buckets.linear_delete)(t, d, dm)
            state, _ = ref.probe_delete_ref(
                *want, hashing.bucket_of(t.hfn, d, c), d, dm, max_probes)
            want = (want[0], want[1], state)
    live = np.asarray(t.state) == 1
    runs = np.diff(np.flatnonzero(np.diff(np.r_[0, live, 0])))[::2]
    assert live.mean() > 0.95 and runs.max() > 64


def test_ordered_lookup_fused_matches_ref():
    """The fused old->hazard->new kernel path == ordered_lookup_ref."""
    rng = np.random.default_rng(7)
    told, keys, _ = _table(1 << 12, 1_500, seed=11)
    tnew, keys2, _ = _table(1 << 12, 1_200, seed=12)
    hk = jnp.asarray(rng.choice(10_000_000, 64, replace=False).astype(np.int32))
    hv = hk * 7
    hl = jnp.asarray(rng.random(64) < 0.7)
    qs = jnp.concatenate([keys[:500], keys2[:500], hk,
                          jnp.asarray(rng.integers(2**30, 2**31 - 1, 300)
                                      .astype(np.int32))])
    h0_old = hashing.bucket_of(told.hfn, qs, told.capacity)
    h0_new = hashing.bucket_of(tnew.hfn, qs, tnew.capacity)
    args = ((told.key, told.val, told.state), (tnew.key, tnew.val, tnew.state),
            hk, hv, hl, h0_old, h0_new, qs)
    f_ref, v_ref = ref.ordered_lookup_ref(*args, max_probes=32)
    f_k, v_k = ops.ordered_lookup(*args, max_probes=32)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref))


# ---------------------------------------------------------------------------
# write-path kernels: delete / extract / land (PR 2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("capacity,n_items,n_del", [
    (1 << 10, 500, 333),          # small, non-tile-aligned delete count
    (1 << 14, 9_000, 4_097),      # multi-tile, odd count
])
def test_probe_delete_matches_jnp(capacity, n_items, n_del):
    """Fused delete == jnp delete on every observable: ok flags, final
    states, membership — batch mixes present keys, absent keys, duplicates,
    and masked-out entries; batch size is not a tile multiple."""
    t, keys, _ = _table(capacity, n_items, seed=capacity % 89)
    rng = np.random.default_rng(2)
    absent = jnp.asarray(rng.integers(20_000_000, 2**31 - 1, n_del // 3)
                         .astype(np.int32))
    batch = jnp.concatenate([keys[:n_del], absent, keys[:64]])[:n_del]
    mask = jnp.ones(batch.shape, bool).at[-17:].set(False)
    t_j, ok_j = jax.jit(buckets.linear_delete)(t, batch, mask)
    t_k, ok_k = jax.jit(buckets.linear_delete_fused)(t, batch, mask)
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_j))
    np.testing.assert_array_equal(np.asarray(t_k.state), np.asarray(t_j.state))
    f_j, _, _ = buckets.linear_lookup(t_j, keys)
    f_k, _, _ = buckets.linear_lookup(t_k, keys)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_j))


def test_probe_delete_tombstone_reuse():
    """Slots freed by the fused delete are reclaimed by the fused insert:
    live count conserved, and every re-inserted key readable."""
    t = buckets.linear_make(256, hashing.fresh("mix32", 0), max_probes=32)
    k = jnp.arange(1, 181, dtype=jnp.int32)
    t, _ = jax.jit(buckets.linear_insert)(t, k, k * 2, jnp.ones(180, bool))
    t, ok_d = jax.jit(buckets.linear_delete_fused)(t, k[:90],
                                                   jnp.ones(90, bool))
    assert bool(ok_d.all())
    assert int((t.state == 2).sum()) == 90          # TOMB
    k2 = jnp.arange(1000, 1090, dtype=jnp.int32)
    t, ok_i = jax.jit(buckets.linear_insert_fused)(t, k2, k2 * 3,
                                                   jnp.ones(90, bool))
    assert bool(ok_i.all())                          # tombstones reused
    assert int(buckets.linear_count_live(t)) == 180
    f, v, _ = buckets.linear_lookup(t, k2)
    assert bool(f.all()) and bool((v == k2 * 3).all())


def test_write_kernels_budget():
    """Budget: each linear write-path op is ONE pallas_call and no sort
    (windowed kernels; extract reads one contiguous chunk); the sorted-slab
    twochoice/cuckoo ops are ONE argsort + ONE pallas_call."""
    t, keys, _ = _table(1 << 12, 1_000, seed=13)
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    mask = jnp.ones(keys.shape, bool)

    jx = jax.make_jaxpr(
        lambda *a: ops.probe_delete(*a, max_probes=32))(
        t.key, t.val, t.state, h0, keys, mask)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 0, "pallas_call": 1}

    args = _ordered_args(n_q=2_048)
    jx = jax.make_jaxpr(
        lambda *a: ops.ordered_delete_fused(*a, max_probes=32))(
        *args, jnp.ones(args[-1].shape, bool))
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 0, "pallas_call": 1}

    jx = jax.make_jaxpr(
        lambda k, v, s, c: ops.extract_chunk_fused(k, v, s, c, chunk=256))(
        t.key, t.val, t.state, jnp.asarray(0, jnp.int32))
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 0, "pallas_call": 1}

    tc = buckets.twochoice_make(1 << 9, hashing.fresh("mix32", 1),
                                hashing.fresh("mix32", 2), width=8)
    ba, bb = buckets._tc_rows(tc, keys)
    jx = jax.make_jaxpr(ops.twochoice_lookup)(
        tc.key, tc.val, tc.state, ba, bb, keys)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}

    jx = jax.make_jaxpr(
        lambda *a: ops.twochoice_insert(*a, max_rounds=8))(
        tc.key, tc.val, tc.state, ba, bb, keys, keys * 2, mask)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}

    # cuckoo rides the SAME two-row kernels with side-offset rows, and its
    # conflict-escape kick loop lives behind a cond: the fused lookup holds
    # the identical 1-sort / 1-pallas_call budget, and the fused insert's
    # counts EQUAL the twochoice adapter's (batch_winners' lexsort + the
    # claim kernel's sort) — the kick adds zero sorts and zero launches
    from repro.core import backend as _backend
    ckt = buckets.cuckoo_make(1 << 8, hashing.fresh("mix32", 3),
                              hashing.fresh("mix32", 4), width=8)
    jx = jax.make_jaxpr(_backend.cuckoo_lookup_fused)(ckt, keys)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}

    jx = jax.make_jaxpr(
        lambda t, k, v, m: _backend.twochoice_insert_fused(t, k, v, m))(
        tc, keys, keys * 2, mask)
    tc_budget = _count_primitives(jx, ("sort", "pallas_call"))
    jx = jax.make_jaxpr(
        lambda t, k, v, m: _backend.cuckoo_insert_fused(t, k, v, m))(
        ckt, keys, keys * 2, mask)
    assert _count_primitives(jx, ("sort", "pallas_call")) == tc_budget
    assert tc_budget["pallas_call"] == 1


@pytest.mark.parametrize("cursor", [0, 100, 4_000, 4_090, 8_100])
def test_extract_chunk_fused_matches_jnp(cursor):
    """Fused extract == jnp extract as a SET (the fused hazard buffer is
    compacted on-device), with identical MIGRATED markings and cursor
    advance — cursor positions cover the slab seam and the table edge."""
    t, keys, _ = _table(1 << 13, 4_000, seed=5, deletes=500)
    cur = jnp.asarray(cursor, jnp.int32)
    t_j, hk_j, hv_j, hl_j, cur_j = jax.jit(
        lambda t, c: buckets.linear_extract_chunk(t, c, 256))(t, cur)
    t_k, hk_k, hv_k, hl_k, cur_k = jax.jit(
        lambda t, c: buckets.linear_extract_chunk_fused(t, c, 256))(t, cur)
    np.testing.assert_array_equal(np.asarray(t_k.state),
                                  np.asarray(t_j.state))
    assert int(cur_j) == int(cur_k)
    lj, lk = np.asarray(hl_j), np.asarray(hl_k)
    set_j = set(zip(np.asarray(hk_j)[lj].tolist(),
                    np.asarray(hv_j)[lj].tolist()))
    set_k = set(zip(np.asarray(hk_k)[lk].tolist(),
                    np.asarray(hv_k)[lk].tolist()))
    assert set_j == set_k
    # compaction: live entries are a prefix
    assert (np.flatnonzero(lk) == np.arange(lk.sum())).all()


def test_ordered_delete_fused_matches_staged():
    """Mid-rebuild fused delete (ONE probe2 pass) == the staged jnp ordered
    delete on ok flags, remaining membership, and item counts — the batch
    hits old-table keys, hazard keys, new-table keys, and absent keys."""
    rng = np.random.default_rng(8)
    d_j = dhash.make("linear", capacity=1024, chunk=128, seed=5, fused=False)
    d_k = dhash.make("linear", capacity=1024, chunk=128, seed=5, fused=True)
    keys = jnp.asarray(rng.choice(100_000, 800, replace=False)
                       .astype(np.int32))
    ins = jax.jit(dhash.insert)
    d_j, _ = ins(d_j, keys, keys * 2)
    d_k, _ = ins(d_k, keys, keys * 2)
    d_j = dhash.rebuild_start(d_j, seed=9)
    d_k = dhash.rebuild_start(d_k, seed=9)
    step = jax.jit(dhash.rebuild_step)
    for _ in range(3):   # extract, land, extract -> populated hazard window
        d_j, d_k = step(d_j), step(d_k)
    assert bool(d_k.hazard_live.any())
    batch = jnp.concatenate([
        keys[::3], jnp.asarray(rng.integers(200_000, 300_000, 101)
                               .astype(np.int32))])
    dl = jax.jit(dhash.delete)
    d_j2, ok_j = dl(d_j, batch)
    d_k2, ok_k = dl(d_k, batch)
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_j))
    assert int(dhash.count_items(d_j2)) == int(dhash.count_items(d_k2))
    look = jax.jit(dhash.lookup)
    f_j, v_j = look(d_j2, keys)
    f_k, v_k = look(d_k2, keys)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_j))
    fm = np.asarray(f_j)
    np.testing.assert_array_equal(np.asarray(v_k)[fm], np.asarray(v_j)[fm])


@pytest.mark.parametrize("backend,fused", [
    ("linear", True), ("twochoice", True), ("chain", True), ("cuckoo", True),
    ("chain", False),
])
def test_delete_extract_land_parity_all_backends(backend, fused):
    """The full write surface (delete + extract + land + swap) against a
    dict oracle for every backend — all four on the fused kernels, plus
    chain on the jnp reference path (the fused chain's fallback target)."""
    rng = np.random.default_rng(3)
    d = dhash.make(backend, capacity=512, chunk=64, seed=7, fused=fused)
    oracle: dict[int, int] = {}
    keys = rng.choice(100_000, 301, replace=False).astype(np.int32)  # odd N
    d, ok = jax.jit(dhash.insert)(d, jnp.asarray(keys), jnp.asarray(keys * 2))
    assert bool(ok.all())
    oracle.update({int(k): int(k) * 2 for k in keys})
    d = dhash.rebuild_start(d, seed=31)
    step = jax.jit(dhash.rebuild_step)
    dl = jax.jit(dhash.delete)
    look = jax.jit(dhash.lookup)
    i = 0
    while bool(jax.device_get(d.rebuilding)) and i < 64:
        d = step(d)                       # extract or land
        dels = keys[i::16][:5]            # delete during the hazard window
        d, ok_d = dl(d, jnp.asarray(dels))
        expect = np.array([int(k) in oracle for k in dels])
        np.testing.assert_array_equal(np.asarray(ok_d), expect)
        for k in dels:
            oracle.pop(int(k), None)
        if bool(jax.device_get(dhash.rebuild_done(d))):
            d = dhash.rebuild_finish(d)
        i += 1
    assert int(d.epoch) == 1, "rebuild did not complete"
    assert int(dhash.count_items(d)) == len(oracle)
    f, v = look(d, jnp.asarray(keys))
    expect_f = np.array([int(k) in oracle for k in keys])
    np.testing.assert_array_equal(np.asarray(f), expect_f)
    np.testing.assert_array_equal(np.asarray(v)[expect_f],
                                  np.array([oracle[int(k)] for k in keys
                                            if int(k) in oracle]))


# (The per-backend fused-vs-jnp parity copies that lived here —
# test_tc_lookup_fused_matches_jnp, test_tc_insert_delete_fused_matches_jnp —
# are subsumed by the registry-parameterized op-contract checklist in
# tests/test_backend_protocol.py, which runs the same assertions for EVERY
# BucketBackend entry x fused on/off.)


def test_land_fused_uses_insert_kernel():
    """rebuild_land on a fused state routes through the claim kernel: the
    landed epoch conserves membership, and the jaxpr of the fused landing
    contains a pallas_call (the jnp landing has none)."""
    d = dhash.make("linear", capacity=512, chunk=64, seed=2, fused=True)
    d_j = dhash.make("linear", capacity=512, chunk=64, seed=2, fused=False)
    jx_f = jax.make_jaxpr(dhash.rebuild_land)(d)
    jx_j = jax.make_jaxpr(dhash.rebuild_land)(d_j)
    assert _count_primitives(jx_f, ("pallas_call",))["pallas_call"] >= 1
    assert _count_primitives(jx_j, ("pallas_call",))["pallas_call"] == 0


# ---------------------------------------------------------------------------
# chain backend: arena-sorted fused path (PR 4)
# ---------------------------------------------------------------------------

def _chain_table(nbuckets=64, arena=2048, n_items=600, seed=1, max_chain=64,
                 compact=True):
    rng = np.random.default_rng(seed)
    t = buckets.chain_make(nbuckets, arena, hashing.fresh("mix32", seed),
                           max_chain=max_chain)
    keys = jnp.asarray(rng.choice(1_000_000, n_items, replace=False)
                       .astype(np.int32))
    t, ok = jax.jit(buckets.chain_insert)(t, keys, keys * 3,
                                          jnp.ones(keys.shape, bool))
    assert bool(ok.all())
    if compact:
        t = buckets.chain_compact_fused(t)
    return t, keys


def test_chain_compact_fused_invariants():
    """Compaction produces bucket-sorted, tombstone-compacted segments with
    valid pointers: per-bucket (start, len) tiles the live prefix, chains
    walk each segment in order, membership is preserved, and dead nodes are
    physically reclaimed."""
    t, keys = _chain_table(compact=False)
    t, _ = jax.jit(buckets.chain_delete)(t, keys[:150], jnp.ones(150, bool))
    tc = buckets.chain_compact_fused(t)
    live = 600 - 150
    assert int(buckets.chain_dirty(tc)) == 0
    assert int(tc.sorted_upto) == live
    assert int(tc.free_top) == tc.arena - live          # tombstones reclaimed
    bstart, blen = np.asarray(tc.bstart), np.asarray(tc.blen)
    assert blen.sum() == live
    np.testing.assert_array_equal(bstart, np.concatenate([[0],
                                                          blen.cumsum()[:-1]]))
    # every node's key hashes to the bucket whose segment holds it
    b_of = np.asarray(hashing.bucket_of(tc.hfn, tc.akey, tc.nbuckets))
    for b in range(tc.nbuckets):
        seg = slice(int(bstart[b]), int(bstart[b] + blen[b]))
        assert (b_of[seg] == b).all()
    # jnp pointer path still sees exactly the surviving keys
    f, v, _ = buckets.chain_lookup(tc, keys)
    np.testing.assert_array_equal(np.asarray(f),
                                  np.arange(600) >= 150)
    np.testing.assert_array_equal(np.asarray(v)[150:],
                                  np.asarray(keys * 3)[150:])


def test_chain_fused_matches_jnp():
    """Fused chain lookup/insert/delete == the jnp pointer-chasing path on
    EVERY observable — including the exact arena state for insert (same
    allocation and link order), with duplicates, re-inserts, masked-out
    entries, and an odd batch size."""
    rng = np.random.default_rng(4)
    t, keys = _chain_table()
    qs = jnp.concatenate([keys, jnp.asarray(
        rng.integers(2_000_000, 3_000_000, 333).astype(np.int32))])
    f_j, v_j, l_j = jax.jit(buckets.chain_lookup)(t, qs)
    f_k, v_k, l_k = jax.jit(buckets.chain_lookup_fused)(t, qs)
    fm = np.asarray(f_j)
    np.testing.assert_array_equal(np.asarray(f_k), fm)
    np.testing.assert_array_equal(np.asarray(v_k)[fm], np.asarray(v_j)[fm])
    np.testing.assert_array_equal(np.asarray(l_k)[fm], np.asarray(l_j)[fm])
    assert (np.asarray(l_k)[~fm] == -1).all()

    fresh = jnp.asarray(rng.choice(np.arange(3_000_000, 4_000_000), 200,
                                   replace=False).astype(np.int32))
    batch = jnp.concatenate([fresh, fresh[:50], keys[:50]])
    mask = jnp.ones(batch.shape, bool).at[-10:].set(False)
    t_j, ok_j = jax.jit(buckets.chain_insert)(t, batch, batch * 7, mask)
    t_k, ok_k = jax.jit(buckets.chain_insert_fused)(t, batch, batch * 7,
                                                    mask)
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_j))
    for fld in ("akey", "aval", "astate", "anext", "heads", "free_top"):
        np.testing.assert_array_equal(np.asarray(getattr(t_k, fld)),
                                      np.asarray(getattr(t_j, fld)),
                                      err_msg=fld)

    dels = jnp.concatenate([keys[:100], fresh[:40], jnp.asarray(
        rng.integers(5_000_000, 6_000_000, 31).astype(np.int32))])
    dm = jnp.ones(dels.shape, bool)
    td_j, okd_j = jax.jit(buckets.chain_delete)(t_j, dels, dm)
    td_k, okd_k = jax.jit(buckets.chain_delete_fused)(t_k, dels, dm)
    np.testing.assert_array_equal(np.asarray(okd_k), np.asarray(okd_j))
    np.testing.assert_array_equal(np.asarray(td_k.astate),
                                  np.asarray(td_j.astate))


def test_chain_kernels_budget():
    """Budget: every fused chain batch op is ONE argsort + ONE pallas_call
    (the dirty-tail window is a dynamic_slice compare, the insert relink is
    a pair of prefix/suffix scans — neither adds a sort), and the
    compaction pass is exactly ONE segmented sort with no kernel launch."""
    t, keys = _chain_table()
    t2, _ = _chain_table(seed=2)
    rng = np.random.default_rng(0)
    hk = jnp.asarray(rng.choice(10_000_000, 64, replace=False)
                     .astype(np.int32))
    hl = jnp.asarray(rng.random(64) < 0.7)
    mask = jnp.ones(keys.shape, bool)
    b = hashing.bucket_of(t.hfn, keys, t.nbuckets)
    b2 = hashing.bucket_of(t2.hfn, keys, t2.nbuckets)
    parts, parts2 = buckets._chain_parts(t), buckets._chain_parts(t2)

    jx = jax.make_jaxpr(lambda *a: ops.chain_lookup_fused(*a, max_chain=64))(
        *parts, b, keys)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}

    jx = jax.make_jaxpr(lambda *a: ops.chain_delete_fused(*a, max_chain=64))(
        *parts, b, keys, mask)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}

    jx = jax.make_jaxpr(lambda *a: ops.chain_insert_fused(*a, max_chain=64))(
        parts[0], parts[1], parts[2], t.free_stack, t.free_top, b,
        keys, keys * 2, mask)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}

    jx = jax.make_jaxpr(
        lambda *a: ops.chain_ordered_lookup(*a, max_chain=64))(
        *parts, *parts2, hk, hk * 7, hl, b, b2, keys)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}

    jx = jax.make_jaxpr(
        lambda *a: ops.chain_ordered_delete(*a, max_chain=64))(
        *parts, *parts2, hk, hk * 7, hl, b, b2, keys, mask)
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 1}

    jx = jax.make_jaxpr(
        lambda *a: ops.chain_compact_fused(*a, nbuckets=t.nbuckets))(
        t.akey, t.aval, t.astate, hashing.bucket_of(t.hfn, t.akey,
                                                    t.nbuckets))
    assert _count_primitives(jx, ("sort", "pallas_call")) == \
        {"sort": 1, "pallas_call": 0}


def test_chain_staleness_forces_fallback_parity():
    """Compaction staleness: a dirty tail grown past ops.DIRTY_CAP makes
    absence unprovable in-pass, so the fused ops must route through the
    gated pointer-chasing fallback — and stay exact across BOTH sides of
    the compaction transition."""
    rng = np.random.default_rng(9)
    t = buckets.chain_make(64, 4096, hashing.fresh("mix32", 9), max_chain=96)
    keys = jnp.asarray(rng.choice(1_000_000, ops.DIRTY_CAP + 188,
                                  replace=False).astype(np.int32))
    t, ok = jax.jit(buckets.chain_insert_fused)(t, keys, keys * 2,
                                                jnp.ones(keys.shape, bool))
    assert bool(ok.all())
    assert int(buckets.chain_dirty(t)) > ops.DIRTY_CAP   # stale: past the window
    qs = jnp.concatenate([keys, jnp.asarray(
        rng.integers(2_000_000, 3_000_000, 101).astype(np.int32))])
    f_j, v_j, _ = jax.jit(buckets.chain_lookup)(t, qs)
    f_k, v_k, _ = jax.jit(buckets.chain_lookup_fused)(t, qs)
    fm = np.asarray(f_j)
    np.testing.assert_array_equal(np.asarray(f_k), fm)
    np.testing.assert_array_equal(np.asarray(v_k)[fm], np.asarray(v_j)[fm])
    # the trigger restores the sorted invariant at exactly this threshold...
    t2 = jax.jit(buckets.chain_maybe_compact)(t)
    assert int(buckets.chain_dirty(t2)) == 0
    # ...and a below-threshold table is left untouched (cond not taken)
    t3 = jax.jit(buckets.chain_maybe_compact)(t2)
    np.testing.assert_array_equal(np.asarray(t3.akey), np.asarray(t2.akey))
    f_c, v_c, _ = jax.jit(buckets.chain_lookup_fused)(t2, qs)
    np.testing.assert_array_equal(np.asarray(f_c), fm)
    np.testing.assert_array_equal(np.asarray(v_c)[fm], np.asarray(v_j)[fm])


def test_chain_ordered_matches_ref_grown_arena():
    """Fused chain rebuild-epoch lookup/delete == the pointer-chasing
    ordered oracle with a 4x-grown, partially-landed new arena carrying a
    dirty tail, live hazard entries, duplicates, and absent keys."""
    rng = np.random.default_rng(1)
    told, k1 = _chain_table(seed=2)
    tnew = buckets.chain_make(256, 8192, hashing.fresh("mix32", 3),
                              max_chain=64)
    k2 = jnp.asarray(rng.choice(np.arange(1_000_000, 2_000_000), 400,
                                replace=False).astype(np.int32))
    tnew, _ = jax.jit(buckets.chain_insert)(tnew, k2, k2 * 5,
                                            jnp.ones(400, bool))
    tnew = buckets.chain_compact_fused(tnew)
    k3 = jnp.asarray(rng.choice(np.arange(4_000_000, 5_000_000), 120,
                                replace=False).astype(np.int32))
    tnew, _ = jax.jit(buckets.chain_insert_fused)(tnew, k3, k3 * 9,
                                                  jnp.ones(120, bool))
    assert int(buckets.chain_dirty(tnew)) == 120
    hk = jnp.asarray(rng.choice(np.arange(6_000_000, 7_000_000), 64,
                                replace=False).astype(np.int32))
    hv, hl = hk * 7, jnp.asarray(rng.random(64) < 0.7)
    qs = jnp.concatenate([k1[:200], k2[:200], k3[:60], hk, jnp.tile(k1[:64], 2),
                          jnp.asarray(rng.integers(8_000_000, 9_000_000, 333)
                                      .astype(np.int32))])
    f_k, v_k = jax.jit(buckets.chain_ordered_lookup_fused)(
        told, tnew, hk, hv, hl, qs)
    bqo = hashing.bucket_of(told.hfn, qs, told.nbuckets)
    bqn = hashing.bucket_of(tnew.hfn, qs, tnew.nbuckets)
    f_r, v_r = ref.chain_ordered_lookup_ref(
        (told.akey, told.aval, told.astate), (told.anext, told.heads),
        (tnew.akey, tnew.aval, tnew.astate), (tnew.anext, tnew.heads),
        hk, hv, hl, bqo, bqn, qs, 64)
    fm = np.asarray(f_r)
    np.testing.assert_array_equal(np.asarray(f_k), fm)
    np.testing.assert_array_equal(np.asarray(v_k)[fm], np.asarray(v_r)[fm])

    dels = jnp.concatenate([k1[::5], k2[::5], k3[::5], hk[:20], jnp.asarray(
        rng.integers(8_000_000, 9_000_000, 41).astype(np.int32))])
    dm = jnp.ones(dels.shape, bool)
    os_, ns_, hl2, ok = jax.jit(buckets.chain_ordered_delete_fused)(
        told, tnew, hk, hv, hl, dels, dm)
    # staged jnp oracle: old -> hazard kill -> new
    winner = buckets.batch_winners(dels, dm)
    t_o2, ok_o = jax.jit(buckets.chain_delete)(told, dels, dm)
    pend = dm & ~ok_o
    eq = (dels[:, None] == hk[None, :]) & hl[None, :]
    hz_hit = eq.any(-1) & pend & winner
    kill = (eq & hz_hit[:, None]).any(0)
    t_n2, ok_n = jax.jit(buckets.chain_delete)(tnew, dels, pend & ~hz_hit)
    np.testing.assert_array_equal(np.asarray(ok),
                                  np.asarray(ok_o | hz_hit | ok_n))
    np.testing.assert_array_equal(np.asarray(os_), np.asarray(t_o2.astate))
    np.testing.assert_array_equal(np.asarray(ns_), np.asarray(t_n2.astate))
    np.testing.assert_array_equal(np.asarray(hl2), np.asarray(hl & ~kill))


def test_nres_cap_overflow_graceful():
    """A 32x-growth rebuild target — more new-table blocks than the
    sorted-slab tile map's NRES_CAP residents — stays exact AND inside the
    one linear probe2 pass: each query reads its own new-table window, so
    the fused ordered lookup has no fallback branch at all, whatever the
    growth."""
    rng = np.random.default_rng(3)
    told, keys, _ = _table(1 << 12, 3_000, seed=21)
    c_new = (1 << 12) * 32                      # 131072 slots = 32 slabs
    tnew = buckets.linear_make(c_new, hashing.fresh("mix32", 22),
                               max_probes=32)
    k2 = jnp.asarray(rng.choice(np.arange(10_000_000, 20_000_000), 3_000,
                                replace=False).astype(np.int32))
    tnew, _ = jax.jit(buckets.linear_insert)(tnew, k2, k2 * 9,
                                             jnp.ones(k2.shape, bool))
    hz = jnp.zeros(32, jnp.int32)
    qs = jnp.concatenate([keys[:1_500], k2[:1_500], jnp.asarray(
        rng.integers(2**30, 2**31 - 1, 1_096).astype(np.int32))])
    h0_old = hashing.bucket_of(told.hfn, qs, told.capacity)
    h0_new = hashing.bucket_of(tnew.hfn, qs, tnew.capacity)
    args = ((told.key, told.val, told.state), (tnew.key, tnew.val, tnew.state),
            hz, hz, jnp.zeros(32, bool), h0_old, h0_new, qs)
    # the growth exceeds what the sorted-slab tile map could hold resident
    nblocks_new = (-(-(c_new + 32) // ops.SLAB) + 1)
    assert nblocks_new - 1 > ops.NRES_CAP
    f_ref, v_ref = ref.ordered_lookup_ref(*args, max_probes=32)
    f_k, v_k = ops.ordered_lookup_fused(*args, max_probes=32)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_ref))
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_ref))
    # one kernel pass, no sort, and no fallback branch to overflow into
    jx = jax.make_jaxpr(
        lambda *a: ops.ordered_lookup_fused(*a, max_probes=32))(*args)
    assert _count_primitives(jx, ("sort", "pallas_call", "cond")) == \
        {"sort": 0, "pallas_call": 1, "cond": 0}


def test_ops_imports_before_core_in_a_fresh_interpreter():
    """``repro.kernels.ops`` imports on its own (no cycle through
    ``repro.core``), as the compile tests and the chip smoke need."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", "import repro.kernels.ops"],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_fused_make_refuses_a_chunk_past_the_slab():
    """A fused table takes a rebuild chunk of at most ``SLAB`` entries (the
    hazard buffer is compared densely against every query); ``make`` says
    so instead of quietly scanning with the jnp ops."""
    with pytest.raises(ValueError, match="rebuild chunk of at most"):
        dhash.make("linear", 1 << 14, chunk=2 * ops.SLAB, fused=True)
    assert dhash.make("linear", 1 << 14, chunk=2 * ops.SLAB,
                      fused=False).chunk == 2 * ops.SLAB
