#!/usr/bin/env python3
"""Bring-up smoke of the DHash table service on a TPU.

Drives the table service through its normal entry points at a deployment
size, for both op sets (``fused=False``: the jnp ops; ``fused=True``: the
Pallas kernels), and checks every answer it samples against a host model.

Per op set:

* **populate** — ``dhash.make("linear", capacity=2**25, chunk=4096)`` is a
  2^26-slot table (768 MiB per table copy, old + new 1.5 GiB); 2^25
  distinct keys go in as 512 insert batches of 65536, every one of which
  must be acknowledged.
* **traffic** — ``DHashEngine(continuous_rebuild=True)``; each step carries
  65536 lookups, 8192 inserts and 8192 deletes (``configs/dhash_paper.py``)
  and the engine's rebuild transition, followed by ``REBUILD_EXTRA`` more
  transitions (``dhash.rebuild_step``), until one complete live rehash has
  swapped epochs and 64 steps more.  A rehash of 2^26 slots is 32768
  transitions (extract + land per 4096-slot chunk); at one per step it
  would not fit the run's time limit, so 17 per step take it to about 1930
  steps.  The total is odd, so every other step meets a live hazard
  buffer.  Inserts are 7168 new keys plus 1024 in-batch
  duplicates (must be refused); deletes are the 7168 oldest keys plus 1024
  absent ones (must be refused), so the live set stays at 2^25 keys.  The
  acknowledgement counts of every step, and every result of at least 64
  steps spread before, during and after the rehash, are compared with the
  model.

The model is plain numpy over the key universe: a presence bitmap and a
value array, updated with set semantics (the first occurrence of a key in a
batch wins).  It shares no code with ``core/buckets.py`` or
``kernels/ref.py``.  Keys and values are derived from ``--seed``.

``--chips 4`` runs only the sharded path instead: ``distributed.
make_stacked`` over a mesh of the four chips, 2^25 keys per shard, filled
through the routed insert, then driven by ``DHashEngine`` (its routed step
per batch, the epoch swap of every shard at its poll) plus
``REBUILD_EXTRA`` more transitions per step on every shard (an odd total,
so the ops meet a live hazard buffer on every other step) until a rehash
has swapped epochs on every shard and 64 steps more, checked against the
same model.  Each shard's tables are built on the chip that holds them;
the smoke fails if a table array is not one shard per chip, or if a chip's
peak memory after populate is above ``PEAK_SLACK`` times one shard's
tables plus the compiled insert's working memory.

Earlier lines print one JSON object per phase (wall time, compilations,
``peak_bytes_in_use``, the share of queries recomputed); the last line is
``{"ok": true, "device": {...}}``.  Off a TPU the script exits non-zero
before any phase.

    python chip_smoke.py [--seed N] [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

LOG2_KEYS = 25               # keys per table (2^26 slots at load 0.75 sizing)
CHUNK = 4096                 # rebuild chunk (configs/dhash_paper.py)
BATCH = 1 << 16              # populate batch and lookups per step
UPDATES = 1 << 13            # inserts and deletes per step
DUPS = 1024                  # refused inserts / deletes per step
CHECK_EVERY = 32             # full result check cadence during the rehash
AFTER = 64                   # steps run after the first epoch swap
PROGRESS_EVERY = 256         # traffic steps between progress lines
SHARD_BATCH = 1 << 17        # keys per shard per routed populate batch
REBUILD_EXTRA = 16           # rebuild transitions per step beyond the op
                             # step's own (an even count: odd total)
RECOMPUTE_LIMIT = 0.01       # largest recomputed share admitted (uniform keys)
PEAK_SLACK = 1.25            # largest peak device memory admitted on the
                             # sharded path, over one shard's tables plus the
                             # compiled populate step's working memory
STEP_IS = f"1 service step + {REBUILD_EXTRA} extra rebuild transitions"
KERNEL_CALL = "tpu_custom_call"   # a Mosaic kernel in compiled HLO


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def peak_bytes(devs) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]


def epoch_notice(chips: int, n: int) -> None:
    """The long-epoch line: how many steps a rehash takes at the service
    step's own rate of one transition, and at the smoke's."""
    transitions = 2 * (2 * n // CHUNK)          # extract + land per chunk
    emit(phase="traffic_plan", chips=chips, step_is=STEP_IS,
         epoch_transitions=transitions,
         epoch_steps_at_one_transition=transitions,
         epoch_steps_here=-(-transitions // (1 + REBUILD_EXTRA)),
         note="at one transition per step the 2^26-slot epoch outlasts the "
              "run's time limit; every timing of this phase is of the "
              "step_is step, not of a plain service step")


# ---------------------------------------------------------------------------
# keys, values and the host model
# ---------------------------------------------------------------------------

def _mix(x: np.ndarray, mul: int, add: int) -> np.ndarray:
    """A bijection of uint32 (odd multiply, add, xor-shift)."""
    x = x.astype(np.uint32) * np.uint32(mul) + np.uint32(add)
    return x ^ (x >> np.uint32(16))


class Keys:
    """Key and value of each id: seed-dependent bijections of uint32, as
    int32 (so every key is distinct and every value is looked up)."""

    def __init__(self, seed: int):
        self.salt = (seed * 0x2545F491 + 0x3C6EF372) & 0xFFFFFFFF

    def key(self, ids: np.ndarray) -> np.ndarray:
        return _mix(ids, 0x9E3779B1, self.salt).view(np.int32)

    def val(self, ids: np.ndarray) -> np.ndarray:
        return _mix(ids, 0x85EBCA77, self.salt ^ 0x165667B1).view(np.int32)


class Model:
    """The reference: presence and value of every id of the universe."""

    def __init__(self, universe: int):
        self.present = np.zeros(universe, bool)
        self.value = np.zeros(universe, np.int32)

    def insert(self, ids: np.ndarray, vals: np.ndarray) -> np.ndarray:
        ok = np.zeros(ids.shape, bool)
        _, first = np.unique(ids, return_index=True)
        ok[first] = ~self.present[ids[first]]
        self.present[ids[ok]] = True
        self.value[ids[ok]] = vals[ok]
        return ok

    def delete(self, ids: np.ndarray) -> np.ndarray:
        ok = np.zeros(ids.shape, bool)
        _, first = np.unique(ids, return_index=True)
        ok[first] = self.present[ids[first]]
        self.present[ids[ok]] = False
        return ok

    def lookup(self, ids: np.ndarray):
        found = self.present[ids]
        return found, np.where(found, self.value[ids], 0)


class Traffic:
    """The steps' batches over a sliding window of ids: ``width`` live ids
    ``[lo, hi)``; each step inserts the next ``fresh`` ids (plus in-batch
    duplicates) and deletes the oldest ``fresh`` (plus absent ids), and
    looks up ids drawn uniformly from ``[lo - width/2, hi + width/2)``."""

    def __init__(self, keys: Keys, width: int, seed: int, *, shards: int = 1):
        self.keys, self.width, self.shards = keys, width, shards
        self.lo, self.hi = width, 2 * width       # ids below `width`: never
        self.fresh = shards * (UPDATES - DUPS)
        self.rng = np.random.default_rng(seed)

    def populate(self, batch: int):
        for a in range(self.lo, self.hi, self.shards * batch):
            ids = np.arange(a, a + self.shards * batch, dtype=np.int64)
            yield ids, self.keys.val(ids)

    def step(self):
        d = self.shards * DUPS
        new = np.arange(self.hi, self.hi + self.fresh, dtype=np.int64)
        ins = np.concatenate([new, new[:d]])
        iv = np.concatenate([self.keys.val(new),
                             self.keys.val(new[:d]) ^ np.int32(0x5BD1E995)])
        old = np.arange(self.lo, self.lo + self.fresh, dtype=np.int64)
        dels = np.concatenate([old, np.arange(self.lo - d, self.lo)])
        look = self.rng.integers(self.lo - self.width // 2,
                                 self.hi + self.width // 2,
                                 self.shards * BATCH)
        self.lo += self.fresh
        self.hi += self.fresh
        return look, ins, iv, dels

    def universe(self, steps: int) -> int:
        return self.hi + steps * self.fresh + self.width // 2


def check(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = np.flatnonzero(got.reshape(-1) != want.reshape(-1))
        raise AssertionError(f"{name}: {bad.size} mismatches of {want.size}"
                             f" (first at {bad[:5].tolist()})")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class Phase:
    """Wall time, backend compilations and device memory of one phase."""

    compiles = 0

    def __init__(self, jax, name: str, **tags):
        self.jax, self.name, self.tags = jax, name, tags

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Phase.compiles
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            stats = self.jax.devices()[0].memory_stats() or {}
            emit(phase=self.name, **self.tags,
                 wall_s=time.perf_counter() - self.t0,
                 compiles=Phase.compiles - self.c0,
                 peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                 **getattr(self, "extra", {}))
        return False


def single_chip(jax, seed: int, fused: bool) -> None:
    import jax.numpy as jnp

    from repro.core import buckets, dhash, hashing
    from repro.core.engine import DHashEngine
    from repro.kernels import ops

    n = 1 << LOG2_KEYS
    keys = Keys(seed)
    traffic = Traffic(keys, n, seed)
    # an epoch is 2 transitions (extract, land) per chunk of the 2^26 slots
    max_steps = 2 * (2 * n // CHUNK) // (1 + REBUILD_EXTRA) + 1024
    model = Model(traffic.universe(max_steps))

    with Phase(jax, "populate", fused=fused) as ph:
        d = dhash.make("linear", capacity=n, chunk=CHUNK, seed=seed,
                       fused=fused)
        ins = jax.jit(dhash.insert, donate_argnums=0)
        acks = []
        for ids, vals in traffic.populate(BATCH):
            model.insert(ids, vals)
            d, ok = ins(d, jnp.asarray(keys.key(ids)), jnp.asarray(vals))
            acks.append(ok.sum())
        acks = np.asarray(jax.device_get(jnp.stack(acks)))
        check("populate acks", acks, np.full(acks.shape, BATCH))
        live = int(jax.device_get(jax.jit(dhash.count_items)(d)))
        check("populate count", live, n)
        ph.extra = {"slots": int(d.old.capacity), "keys": live}

    def shares(st, ik):
        """Shares of the next step's insert and of the next hazard landing
        that a second claim pass recomputes (the linear lookup, delete and
        extract never recompute a query)."""
        def of(t, k, m):
            h0 = hashing.bucket_of(t.hfn, k, t.capacity)
            return ops.insert_retry_share(t.key, t.state, h0, k, m,
                                             max_probes=t.max_probes)
        win = buckets.batch_winners(ik, jnp.ones(ik.shape, bool))
        user = jax.lax.cond(st.rebuilding, lambda: of(st.new, ik, win),
                            lambda: of(st.old, ik, win))
        land = of(st.new, st.hazard_key, st.hazard_live)
        return user, land, st.hazard_live.any()

    shares = jax.jit(shares)
    counts = jax.jit(lambda ok_i, ok_d: jnp.stack([ok_i.sum(), ok_d.sum()]))
    more = jax.jit(lambda st: jax.lax.fori_loop(
        0, REBUILD_EXTRA, lambda _, dd: dhash.rebuild_step(dd), st),
        donate_argnums=0)

    epoch_notice(1, n)
    with Phase(jax, "traffic", fused=fused) as ph:
        eng = DHashEngine(d, continuous_rebuild=True, rebuild_seed=seed + 7)
        del d
        step, swapped_at, checked = 0, None, []
        pending, want = [], []
        user_sh, land_sh = [], []
        after_sample = False
        t_first = t_swap = None
        while swapped_at is None or step < swapped_at + AFTER:
            look, ins_ids, iv, del_ids = traffic.step()
            lk, ik = keys.key(look), keys.key(ins_ids)
            dk = keys.key(del_ids)
            sample = (step < 32 and step % 8 == 0) or step % CHECK_EVERY == 0 \
                or (swapped_at is not None and step % 8 == 0)
            if sample:
                exp_f, exp_v = model.lookup(look)
            # a step takes an odd number of transitions, so of a sampled
            # step and the next one, one meets a hazard chunk to land
            if fused and (sample or after_sample):
                u, la, landing = jax.device_get(
                    shares(eng.state, jnp.asarray(ik)))
                user_sh.append(float(u))
                if landing:
                    land_sh.append(float(la))
            after_sample = sample
            exp_i = model.insert(ins_ids, iv)
            exp_d = model.delete(del_ids)
            out = eng.step(lk, ik, iv, dk)
            eng.state = more(eng.state)
            pending.append(counts(out[2], out[3]))
            want.append((exp_i.sum(), exp_d.sum()))
            if step == 0:
                fn = eng._step_fn
                txt = fn.lower(eng.state, *(jnp.asarray(a) for a in (
                    lk, ik, iv, dk)), jnp.ones(ik.shape, bool),
                    jnp.ones(dk.shape, bool)).compile().as_text()
                if (KERNEL_CALL in txt) != fused:
                    raise AssertionError(
                        f"compiled step holds {KERNEL_CALL}: "
                        f"{KERNEL_CALL in txt}, fused={fused}")
                t_first = time.perf_counter()
            if sample:
                f, v, oi, od = jax.device_get(out)
                check(f"step {step} found", f, exp_f)
                check(f"step {step} vals", v, exp_v)
                check(f"step {step} insert ok", oi, exp_i)
                check(f"step {step} delete ok", od, exp_d)
                checked.append(step)
            step += 1
            if step % eng.poll_every == 0:
                got = np.asarray(jax.device_get(jnp.stack(pending)))
                check(f"acks of steps {step - len(pending)}..{step - 1}",
                      got, np.asarray(want))
                pending, want = [], []
            if swapped_at is None and step % eng.poll_every == 0 \
                    and eng.stats.rebuilds_completed >= 1:
                swapped_at, t_swap = step, time.perf_counter()
            if step % PROGRESS_EVERY == 0:
                emit(phase="traffic_progress", fused=fused, step=step,
                     steps_per_s=(step - 1) / (time.perf_counter() - t_first))
            if step >= max_steps:
                raise AssertionError(f"no epoch swap after {step} steps")
        if pending:
            got = np.asarray(jax.device_get(jnp.stack(pending)))
            check("acks of the last steps", got, np.asarray(want))
        live = eng.count()
        check("live count", live, int(model.present.sum()))
        if fused and max(user_sh + land_sh) > RECOMPUTE_LIMIT:
            raise AssertionError(f"recomputed share above {RECOMPUTE_LIMIT}: "
                                 f"insert {max(user_sh)}, land "
                                 f"{max(land_sh, default=0.0)}")
        ph.extra = {
            "steps": step, "swap_seen_at_step": swapped_at,
            "epochs": eng.stats.rebuilds_completed,
            "checked_steps": len(checked),
            "checked_before_rehash": sum(s < 32 for s in checked),
            "checked_after_swap": sum(s >= swapped_at for s in checked),
            "steps_per_s": (step - 1) / (time.perf_counter() - t_first),
            "epoch_wall_s": t_swap - t_first,
            "step_is": STEP_IS,
            "recomputed_share": None if not fused else {
                "insert_mean": float(np.mean(user_sh)),
                "insert_max": max(user_sh),
                "land_mean": float(np.mean(land_sh)) if land_sh else None,
                "land_max": max(land_sh, default=None),
                "never_recomputed": ["lookup", "delete", "extract"]},
            "live_keys": live}


def four_chips(jax, seed: int) -> None:
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import dhash, distributed, hashing
    from repro.core.engine import DHashEngine

    devs = jax.devices()
    s = len(devs)
    if s != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {s}")
    mesh = Mesh(np.asarray(devs), ("shard",))
    n = 1 << LOG2_KEYS
    keys = Keys(seed)
    traffic = Traffic(keys, s * n, seed, shards=s)
    max_steps = 2 * (2 * n // CHUNK) // (1 + REBUILD_EXTRA) + 1024
    model = Model(traffic.universe(max_steps))
    owner = hashing.fresh("mix32", seed + 99)
    shard = NamedSharding(mesh, P("shard"))

    def smap(f, n_in, n_out):
        out = P("shard") if n_out == 1 else (P("shard"),) * n_out
        return jax.shard_map(f, mesh=mesh, in_specs=(P("shard"),) * n_in,
                             out_specs=out, check_vma=False)

    def ins_body(st, k, v):
        # set-up routes at a tight cap with a compact slab: a key the slab
        # cannot carry comes back refused, and the acks check catches it
        d, ok, _ = distributed.routed_update(
            distributed.peel(st), k, v, jnp.ones(k.shape, bool), "shard",
            owner, op=dhash.insert,
            cap=distributed.route_cap(1.25, k.size, s), spill_slack=1 / 64)
        return distributed.unpeel(d), ok

    def more_body(st):
        return distributed.unpeel(jax.lax.fori_loop(
            0, REBUILD_EXTRA, lambda _, dd: dhash.rebuild_step(dd),
            distributed.peel(st)))

    def per_key(x):
        return jax.device_put(x, shard)

    with Phase(jax, "populate_sharded", chips=s) as ph:
        st = distributed.make_stacked(s, "linear", capacity=n, chunk=CHUNK,
                                      seed=seed, fused=True, sharding=shard)
        leaves = jax.tree_util.tree_leaves(st)
        misplaced = [a.shape for a in leaves if sorted(
            (sh.device.id, sh.data.shape[0]) for sh in a.addressable_shards)
            != sorted((d.id, 1) for d in devs)]
        if misplaced:
            raise AssertionError(f"{len(misplaced)} table arrays not one "
                                 f"shard per device: {misplaced[:3]}")
        shard_bytes = sum(a.nbytes for a in leaves) // s
        batch = jax.ShapeDtypeStruct((s * SHARD_BATCH,), jnp.int32,
                                     sharding=shard)
        ins = jax.jit(smap(ins_body, 3, 2), donate_argnums=0).lower(
            st, batch, batch).compile()
        budget = shard_bytes + ins.memory_analysis().temp_size_in_bytes
        acks = []
        for ids, vals in traffic.populate(SHARD_BATCH):
            model.insert(ids, vals)
            st, ok = ins(st, per_key(keys.key(ids)), per_key(vals))
            acks.append(ok.sum())
        acks = np.asarray(jax.device_get(jnp.stack(acks)))
        check("populate acks", acks, np.full(acks.shape, s * SHARD_BATCH))
        live = jax.device_get(jax.jit(dhash.stack_count_items)(st))
        check("populate count", int(np.sum(live)), s * n)
        peaks = peak_bytes(devs)
        if max(peaks) > PEAK_SLACK * budget:
            raise AssertionError(
                f"peak bytes per device {peaks} above {PEAK_SLACK} x one "
                f"shard's tables and the insert's working memory ({budget} "
                f"B): a device held more than its own shard")
        ph.extra = {"slots_per_shard": int(st.old.capacity),
                    "keys_per_shard": np.asarray(live).tolist(),
                    "table_bytes_per_shard": shard_bytes,
                    "insert_budget_bytes": budget,
                    "peak_bytes_per_device": peaks}

    epoch_notice(s, n)
    with Phase(jax, "traffic_sharded", chips=s) as ph:
        eng = DHashEngine(st, continuous_rebuild=True, rebuild_seed=seed + 7,
                          owner=owner)
        del st
        more = jax.jit(smap(more_body, 1, 1), donate_argnums=0)
        step, swapped_at, checked, t0 = 0, None, 0, time.perf_counter()
        while swapped_at is None or step < swapped_at + AFTER:
            look, ins_ids, iv, del_ids = traffic.step()
            sample = step % CHECK_EVERY == 0 or (swapped_at is not None
                                                 and step % 8 == 0)
            if sample:
                exp_f, exp_v = model.lookup(look)
            exp_i = model.insert(ins_ids, iv)
            exp_d = model.delete(del_ids)
            out = eng.step(keys.key(look), keys.key(ins_ids), iv,
                           keys.key(del_ids))
            eng.state = more(eng.state)
            if sample:
                f, v, oi, od = jax.device_get(out)
                check(f"step {step} found", f, exp_f)
                check(f"step {step} vals", v, exp_v)
                check(f"step {step} insert ok", oi, exp_i)
                check(f"step {step} delete ok", od, exp_d)
                checked += 1
            step += 1
            if swapped_at is None and step % eng.poll_every == 0 \
                    and eng.stats.rebuilds_completed >= 1:
                swapped_at = step
            if step >= max_steps:
                raise AssertionError(f"no routed epoch swap in {step} steps")
        live = eng.count()
        check("live count", live, int(model.present.sum()))
        ph.extra = {"steps": step, "swap_seen_at_step": swapped_at,
                    "checked_steps": checked,
                    "steps_per_s": step / (time.perf_counter() - t0),
                    "step_is": STEP_IS, "live_keys": live,
                    "routed_lookup_rounds": eng.stats.routed_lookup_rounds,
                    "routed_lanes": eng.stats.routed_lanes,
                    "routed_peak_load": eng.stats.routed_peak_load,
                    "peak_bytes_per_device": peak_bytes(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    emit(phase="setup", compile_cache=compile_cache.enable(),
         device_kind=dev.device_kind, devices=len(jax.devices()))

    def count_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            Phase.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(count_compile)
    if args.chips == 4:
        four_chips(jax, args.seed)
    else:
        for fused in (False, True):
            single_chip(jax, args.seed, fused)
    emit(ok=True, device={"platform": dev.platform, "kind": dev.device_kind,
                          "count": len(jax.devices())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
