"""The traffic generator, the key distributions and the reference model,
on the CPU."""
from __future__ import annotations

import numpy as np
import pytest

from bench import cells
from bench.keys import Keys, Model

zipf = cells.module("distributions", "scrambled_zipfian")
window = cells.module("generators", "sliding_window")
YCSB_ITEM_COUNT, fnv64, zeta = zipf.YCSB_ITEM_COUNT, zipf.fnv64, zipf.zeta
zipfian_ranks = zipf.zipfian_ranks

YCSB_ZETAN = 26.46902820178302      # YCSB ScrambledZipfianGenerator.ZETAN


def test_zeta_matches_ycsb_constant():
    assert zeta(YCSB_ITEM_COUNT, 0.99) == pytest.approx(YCSB_ZETAN, rel=1e-9)
    assert zeta(1000, 0.99) == pytest.approx(
        sum(i ** -0.99 for i in range(1, 1001)), rel=1e-12)


def test_fnv64_matches_ycsb():
    def ref(v):                       # Utils.fnvhash64, on Python ints
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * 1099511628211) & (2**64 - 1)
            v >>= 8
        return abs(h - 2**64 if h >= 2**63 else h)

    xs = np.array([0, 1, 2, 255, 256, 10**9, YCSB_ITEM_COUNT - 1], np.int64)
    assert fnv64(xs).tolist() == [ref(int(x)) for x in xs]


def test_zipfian_top_ranks_follow_the_constant():
    theta = 0.99
    n = 400_000
    ranks = zipfian_ranks(np.random.default_rng(3), n, YCSB_ITEM_COUNT, theta)
    zetan = zeta(YCSB_ITEM_COUNT, theta)
    freq = np.bincount(ranks[ranks < 8], minlength=8) / n
    want = 1.0 / np.arange(1, 9) ** theta / zetan
    # ranks 0 and 1 are drawn exactly; the rest by Gray et al.'s estimate
    assert freq[:2] == pytest.approx(want[:2], rel=0.03)
    assert freq[2:] == pytest.approx(want[2:], rel=0.25)


def test_scrambled_zipfian_hot_records():
    n, records = 400_000, 1 << 20
    assert zipf.ZIPFIAN_CONSTANT == 0.99
    ids = zipf.draw(np.random.default_rng(5), n, records)
    assert ids.min() >= 0 and ids.max() < records
    top = np.sort(np.bincount(ids))[::-1][:2] / n
    zetan = zeta(YCSB_ITEM_COUNT, 0.99)
    assert top[0] == pytest.approx(1 / zetan, rel=0.05)
    assert top[1] == pytest.approx(0.5 ** 0.99 / zetan, rel=0.05)
    # scrambled: the hottest record is rank 0's FNV image, not record 0
    assert np.argmax(np.bincount(ids)) == fnv64(np.zeros(1, np.int64))[0] \
        % records


def test_model_matches_a_dict_set():
    rng = np.random.default_rng(11)
    model, ref = Model(), {}
    for _ in range(200):
        op = rng.integers(3)
        ids = rng.integers(0, 300, rng.integers(1, 40))
        if op == 0:
            vals = rng.integers(-2**31, 2**31, ids.size).astype(np.int32)
            got = model.insert(ids, vals)
            want, seen = [], set()
            for i, v in zip(ids.tolist(), vals.tolist()):
                ok = i not in seen and i not in ref
                seen.add(i)
                if ok:
                    ref[i] = v
                want.append(ok)
        elif op == 1:
            got = model.delete(ids)
            want, seen = [], set()
            for i in ids.tolist():
                ok = i not in seen and i in ref
                seen.add(i)
                if ok:
                    del ref[i]
                want.append(ok)
        else:
            f, v = model.lookup(ids)
            got = np.stack([f, v])
            want = [[i in ref for i in ids.tolist()],
                    [ref.get(i, 0) for i in ids.tolist()]]
        assert np.asarray(got).tolist() == np.asarray(want).tolist()
    assert model.live() == len(ref)


def test_traffic_is_a_function_of_seed_and_index():
    mix = cells.traffic("paper_mix.rehash")
    a = window.Traffic(mix, 1 << 12, 2**31 + 5)
    b = window.Traffic(mix, 1 << 12, 2**31 + 5)
    for i in (0, 7, 100):
        x, y = a.batch(i), b.batch(i)
        assert all(np.array_equal(getattr(x, f), getattr(y, f))
                   for f in ("look", "ins", "ins_vals", "dels"))
    c = window.Traffic(mix, 1 << 12, 2**31 + 6)
    assert not np.array_equal(a.batch(3).look, c.batch(3).look)
    assert a.batch(3).ops == c.batch(3).ops == 65536 + 8192 + 8192


def test_sliding_window_keeps_the_live_set():
    mix = dict(lookups=64, inserts=32, deletes=32, duplicate_inserts=4,
               absent_deletes=4, lookup_range=(-0.5, 1.5))
    t = window.Traffic(mix, 256, 9)
    model = Model()
    model.fill(*t.populate_range(), t.ids)
    for i in range(50):
        b = t.batch(i)
        assert model.insert(b.ins_ids, b.ins_vals).sum() == 28
        assert model.delete(b.del_ids).sum() == 28
        assert model.live() == 256
        assert np.array_equal(t.ids.key(b.ins_ids), b.ins)


def test_device_keys_match_host_keys():
    import jax.numpy as jnp

    from bench.keys import salt_of
    from bench.table import device_keys
    seed = 2**31 + 99
    ids = np.arange(2**20, 2**20 + 1000, dtype=np.int64)
    k, v = device_keys(jnp.asarray(ids, jnp.uint32), jnp.uint32(salt_of(seed)))
    keys = Keys(seed)
    assert np.array_equal(np.asarray(k), keys.key(ids))
    assert np.array_equal(np.asarray(v), keys.val(ids))
