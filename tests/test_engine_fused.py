"""Engine acceptance tests for the on-device steady state: K-step deferred
polling, buffer donation without retraces, zero host syncs between polls,
the epoch swap and the next rebuild start at the poll, and the fused
(Pallas-kernel) state driven end-to-end against a dict oracle."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core import dhash
from repro.core.engine import DHashEngine

I32 = np.int32


def _z1():
    return np.zeros(1, I32)


def _quiet_step(eng, look):
    """An op batch that only looks up (masked-out insert/delete)."""
    return eng.step(look, _z1(), _z1(), _z1(),
                    ins_mask=np.zeros(1, bool), del_mask=np.zeros(1, bool))


def test_zero_host_sync_between_polls(monkeypatch):
    """Steady state: zero device_get for K-1 of every K steps (the poll step
    itself performs exactly one batched device_get)."""
    eng = DHashEngine(dhash.make("linear", capacity=512, chunk=32, seed=7),
                      poll_every=8)
    keys = np.arange(1, 65, dtype=I32)
    eng.step(keys, keys, keys * 2, _z1(), del_mask=np.zeros(1, bool))

    calls = {"n": 0}
    orig = jax.device_get

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)
    for _ in range(16):
        _quiet_step(eng, keys)
    monkeypatch.undo()
    # steps 2..17 -> polls at steps 8 and 16 only
    assert calls["n"] == 2, calls
    assert eng._stats.host_syncs >= 2


def test_donation_no_retrace():
    """The donated step stays on one compiled executable across many steps
    (one cache entry per batch-shape signature, none added by stepping)."""
    eng = DHashEngine(dhash.make("linear", capacity=512, chunk=32, seed=7))
    keys = np.arange(1, 65, dtype=I32)
    for _ in range(12):
        eng.step(keys, keys, keys * 2, keys[:8])
    assert eng._step_cache_size() == 1


def test_deferred_poll_never_misses_epoch_swap():
    """K-step deferred polling: the swap happens at the first poll after
    the rebuild completes; item counts are conserved and every key stays
    readable through the whole rebuild window."""
    rng = np.random.default_rng(0)
    eng = DHashEngine(dhash.make("linear", capacity=512, chunk=32, seed=3),
                      poll_every=32)
    keys = rng.choice(100_000, 300, replace=False).astype(I32)
    for i in range(0, 300, 64):
        b = keys[i:i + 64]
        eng.step(b, b, b * 2, _z1(), del_mask=np.zeros(1, bool))
    assert eng.count() == 300
    epoch0 = int(jax.device_get(eng.state.epoch))
    assert eng.request_rebuild(seed=5)
    syncs0 = eng._stats.host_syncs
    steps = 0
    while bool(jax.device_get(eng.state.rebuilding)):
        f, v, _, _ = _quiet_step(eng, keys[:64])
        assert bool(np.asarray(f).all()), "lookup missed mid-rebuild"
        assert bool((np.asarray(v) == keys[:64] * 2).all())
        steps += 1
        assert steps < 500
    # the poll swapped the epochs and lost nothing
    assert int(jax.device_get(eng.state.epoch)) == epoch0 + 1
    # the host only polled every K steps during the whole rebuild
    assert eng._stats.host_syncs - syncs0 <= steps // eng.poll_every + 1
    assert eng.count() == 300
    assert eng.stats.rebuilds_completed == 1


@pytest.mark.parametrize("fused", [False, True], ids=["jnp", "fused"])
def test_swap_lands_at_first_poll_after_done(fused):
    """The epoch swap runs at the first poll after ``rebuild_done`` holds,
    not before: through the steps between, every key stays readable (the
    ordered check answers for the completed, unswapped rebuild) and inserts
    keep landing; counts are conserved, and each epoch of continuous mode
    runs on a fresh hash function."""
    k = 12
    eng = DHashEngine(dhash.make("linear", capacity=256, chunk=32, seed=5,
                                 fused=fused),
                      continuous_rebuild=True, poll_every=k)
    rng = np.random.default_rng(8)
    keys = rng.choice(100_000, 150, replace=False).astype(I32)
    eng.step(keys, keys, keys * 2, _z1(), del_mask=np.zeros(1, bool))
    done_fn = jax.jit(dhash.rebuild_done)
    seeds = [np.asarray(jax.device_get(eng.state.new.hfn.seeds))]
    swaps, done_at, waited = [], None, 0
    for step in range(2, 100):
        extra = np.array([200_000 + step], I32)
        f, v, ok_i, _ = eng.step(keys, extra, extra * 2, _z1(),
                                 del_mask=np.zeros(1, bool))
        assert bool(np.asarray(f).all()), f"lookup missed at step {step}"
        assert bool((np.asarray(v) == keys * 2).all())
        assert bool(np.asarray(ok_i).all())
        epoch = int(jax.device_get(eng.state.epoch))
        if epoch > len(swaps):                   # the poll swapped
            assert step % k == 0 and done_at is not None
            assert step == -(-done_at // k) * k
            swaps.append(step)
            done_at = None
            seeds.append(np.asarray(jax.device_get(eng.state.new.hfn.seeds)))
        elif bool(jax.device_get(done_fn(eng.state))):
            done_at = step if done_at is None else done_at
            waited += 1
    assert len(swaps) >= 2 and waited > 0
    assert eng.stats.rebuilds_completed == len(swaps)
    assert eng.count() == 150 + 98
    assert len({s.tobytes() for s in seeds}) == len(seeds), \
        "an epoch reused a hash function"


def test_continuous_autostart_on_device_and_reseed():
    """Continuous mode cycles rebuilds with ZERO host involvement between
    polls (the poll swaps and starts the next rebuild); each epoch gets a
    fresh hash function."""
    eng = DHashEngine(dhash.make("linear", capacity=256, chunk=64, seed=1),
                      continuous_rebuild=True, poll_every=32)
    keys = np.arange(1, 101, dtype=I32)
    seeds0 = np.asarray(jax.device_get(eng.state.old.hfn.seeds))
    eng.step(keys, keys, keys * 2, _z1(), del_mask=np.zeros(1, bool))
    for _ in range(40):
        f, _, _, _ = _quiet_step(eng, keys)
        assert bool(np.asarray(f).all())
    assert eng.stats.rebuilds_completed >= 1
    assert eng.count() == 100
    seeds1 = np.asarray(jax.device_get(eng.state.old.hfn.seeds))
    assert not np.array_equal(seeds0, seeds1), "autostart did not reseed"


def test_fused_engine_matches_dict_oracle():
    """End-to-end: fused (Pallas kernel) state in a continuous-rebuild engine
    against a dict oracle — mixed inserts/deletes/lookups across epochs."""
    rng = np.random.default_rng(2)
    eng = DHashEngine(dhash.make("linear", capacity=256, chunk=32, seed=4,
                                 fused=True),
                      continuous_rebuild=True, poll_every=8)
    oracle: dict[int, int] = {}
    universe = np.arange(1, 200)
    for step in range(24):
        ins = rng.choice(universe, 6, replace=False)
        ins = np.array([k for k in ins if k not in oracle] or [0], I32)
        dels = np.array([k for k in rng.choice(list(oracle) or [0], 3)
                         if k in oracle] or [0], I32)
        dels = np.unique(dels)
        look = rng.choice(universe, 16, replace=False).astype(I32)
        pre = dict(oracle)
        found, vals, ok_i, ok_d = eng.step(look, ins, ins * 3, dels,
                                           ins_mask=ins > 0,
                                           del_mask=dels > 0)
        for k in ins[ins > 0]:
            oracle[int(k)] = int(k) * 3
        for k in dels[dels > 0]:
            oracle.pop(int(k), None)
        fn, vn = np.asarray(found), np.asarray(vals)
        for i, k in enumerate(look):
            assert fn[i] == (int(k) in pre), (step, k)
            if int(k) in pre:
                assert vn[i] == pre[int(k)]
    assert eng.count() == len(oracle)


def test_zero_host_sync_full_fused_write_epoch(monkeypatch):
    """Acceptance (PR 2): a FUSED state driving complete rebuild epochs —
    extract kernel -> landing via the claim kernel -> swap at the poll —
    with interleaved lookup/insert/DELETE batches performs ZERO host syncs
    between poll intervals: exactly one batched device_get per poll_every
    steps (the swap and the next rebuild start add none), while at least
    one full epoch completes."""
    eng = DHashEngine(dhash.make("linear", capacity=256, chunk=64, seed=9,
                                 fused=True),
                      continuous_rebuild=True, poll_every=8)
    rng = np.random.default_rng(0)
    keys = rng.choice(50_000, 128, replace=False).astype(I32)
    eng.step(keys, keys, keys * 2, _z1(), del_mask=np.zeros(1, bool))

    calls = {"n": 0}
    orig = jax.device_get

    def counting(x):
        calls["n"] += 1
        return orig(x)

    monkeypatch.setattr(jax, "device_get", counting)
    for i in range(24):
        # mixed traffic: lookups + fresh inserts + deletes of earlier keys
        ins = rng.integers(100_000, 200_000, 8).astype(I32)
        dels = keys[(i * 4) % 128:][:4]
        eng.step(keys[:32], ins, ins * 2, dels)
    monkeypatch.undo()
    # steps 2..25 -> polls at steps 8, 16, 24 only
    assert calls["n"] == 3, calls
    # the epochs cycled while the host only polled
    assert eng.stats.rebuilds_completed >= 1


def test_fused_twochoice_engine_matches_dict_oracle():
    """The twochoice backend on the fused kernels, driven end-to-end in a
    continuous-rebuild engine against a dict oracle (PR 2 brought twochoice
    onto the fused path; the chain backend's engine-level coverage lives in
    tests/test_differential.py)."""
    rng = np.random.default_rng(6)
    eng = DHashEngine(dhash.make("twochoice", capacity=256, chunk=32, seed=4,
                                 fused=True),
                      continuous_rebuild=True, poll_every=8)
    oracle: dict[int, int] = {}
    universe = np.arange(1, 200)
    for step in range(16):
        ins = rng.choice(universe, 6, replace=False)
        ins = np.array([k for k in ins if k not in oracle] or [0], I32)
        dels = np.array([k for k in rng.choice(list(oracle) or [0], 3)
                         if k in oracle] or [0], I32)
        dels = np.unique(dels)
        look = rng.choice(universe, 16, replace=False).astype(I32)
        pre = dict(oracle)
        found, vals, ok_i, ok_d = eng.step(look, ins, ins * 3, dels,
                                           ins_mask=ins > 0,
                                           del_mask=dels > 0)
        for k in ins[ins > 0]:
            oracle[int(k)] = int(k) * 3
        for k in dels[dels > 0]:
            oracle.pop(int(k), None)
        fn, vn = np.asarray(found), np.asarray(vals)
        for i, k in enumerate(look):
            assert fn[i] == (int(k) in pre), (step, k)
            if int(k) in pre:
                assert vn[i] == pre[int(k)]
    assert eng.count() == len(oracle)
