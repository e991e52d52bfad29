"""DHash: a dynamic hash table whose hash function can be rebuilt live.

This is the paper's core contribution (§3-§4) mapped to the SPMD/XLA model:

* The table state is a pytree carrying the *old* table, the *new* table
  (pre-allocated with the replacement hash function), and a **hazard buffer**
  — the batched analogue of the paper's ``rebuild_cur`` global pointer.  A
  rebuild migrates a *chunk* of entries per transition instead of one node
  (single-node granularity would waste the vector units; the hazard period is
  a chunk-sized window).

* ``rebuild_extract`` removes a chunk from the old table into the hazard
  buffer (entries are then in *neither* table — the hazard period, Fig 1c);
  ``rebuild_land`` inserts the hazard entries into the new table and clears
  the buffer (Fig 1d).  The engine interleaves full-rate lookup/insert/delete
  batches between these transitions, which is exactly the concurrency
  structure of the paper; dataflow ordering plays the role of the paper's
  smp_wmb/smp_rmb pairs.

* Every operation performs the paper's **ordered check** (Lemma 4.1/4.2):
      old table  →  hazard buffer  →  new table.
  Lookup priority is old > hazard > new; delete tries old, then marks hazard
  entries dead (the LOGICALLY_REMOVED bit on an in-flight node, Alg. 5 line
  75 — a killed hazard entry is silently dropped at landing), then tries new.
  Insert targets the new table iff a rebuild is in progress (Lemma 4.3/4.4);
  duplicate keys discovered at landing are dropped in favour of the new
  table's copy (Alg. 3 lines 34-36).

* The epoch swap (Alg. 3 lines 41-46) is a host-level transition
  (``rebuild_finish``, or ``epoch_swap`` where the caller has read
  ``rebuild_done`` itself): an O(1) swap of the two tables' pytrees, valid
  whatever their static shapes.  ``DHashEngine`` runs it at its poll.
  ``finish_same_shape`` is the jitted swap of same-shape tables, for
  callers that cannot leave the device between steps (table stacks, the
  policy engine, the router); it selects every table array, so it costs
  whole-table passes on every call.  The paper's ``synchronize_rcu`` grace
  periods are step boundaries: a transition consumes state_t and produces
  state_{t+1}, so no reader of state_t can observe state_{t+1} — the grace
  period is free.

* **No whole table through a conditional.**  A ``lax.cond`` that hands a
  table on unwritten in one branch, while another branch writes it in
  place, makes XLA copy the whole table into or out of the branches (copy
  insertion).  So the linear backend's insert is one claim loop over both
  tables whose claim pass reads the target inside a ``lax.cond`` that
  returns only [Q] lanes (the descriptor's ``insert_either`` hook), and
  its landing runs outside the rebuild step's conditional.  The census in
  ``tests/test_tpu_compile.py`` holds the engine's step and the lookup,
  compiled for a v5e at 2^26 slots, to no whole-table ``copy`` or
  ``select``.

* **Backend dispatch is the descriptor registry** (core/backend.py): every
  op below resolves ``DHashState.backend`` to a frozen ``BucketBackend``
  entry and calls its plain/fused/ordered callables — this module contains
  zero per-backend branches, which is what keeps the paper's modularity
  claim real (a new backend is one ``backend.register()`` call).

* **Table stacks** (``make_stack`` + the ``stack_*`` ops): because each
  backend's state is a uniform pytree with all statics held by the
  descriptor, a stack of T independent tables is just the same pytree with
  a leading [T] axis, and every op ``jax.vmap``s over it — T tables served
  by ONE kernel launch per op, each table free to run its own rebuild epoch
  (multi-tenant serving: per-tenant page tables in serving/kvcache.py).

* **Named scopes**: each public op runs under a ``jax.named_scope`` —
  ``dhash.lookup`` (also ``lookup_counted``), ``dhash.insert``,
  ``dhash.delete``, ``dhash.rebuild_step``, ``dhash.finish_same_shape``,
  ``dhash.rebuild_autostart`` — and the jnp hazard-buffer work inside a
  lookup or delete under a nested ``dhash.hazard``.  Scopes are HLO
  metadata only (no op, fusion or count changes); a profiler trace keeps
  them as each device op's ``tf_op``, so every caller's trace (engines,
  stacks under ``vmap``, the router, the serving page tables) names the
  DHash operation each op belongs to.

Progress-guarantee analogue (DESIGN.md §2): a step's latency is bounded and
independent of rebuild progress — rebuild costs O(chunk) per transition,
never a stop-the-world O(N) pause.
"""
from __future__ import annotations

import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as backends
from repro.core import buckets
from repro.core.struct_utils import pytree_dataclass, replace
from repro.kernels import probe

I32 = jnp.int32


@pytree_dataclass(meta_fields=("backend", "chunk", "fwd_hazard", "fused",
                               "nres_cap"))
class DHashState:
    backend: str                # registry key (core/backend.py)
    chunk: int                  # hazard buffer capacity (entries per rebuild chunk)
    fwd_hazard: bool            # backends with a lookup_fwd hook (linear):
                                # resolve hazard hits via MIGRATED-slot
                                # forwarding (zero extra passes)
    fused: bool                 # route the FULL op surface (lookup/insert/
                                # delete + rebuild extract and land) through
                                # the descriptor's Pallas adapters; every
                                # backend's rebuild-epoch lookup AND delete
                                # is ONE sort + ONE pallas_call
    nres_cap: int               # resident new-table blocks per query tile in
                                # the rebuild-epoch probe (two-level tile
                                # map) — descriptor default, overridable per
                                # table at make()
    old: Any                    # active table (backend pytree)
    new: Any                    # target table; meaningful only while rebuilding
    hazard_key: jax.Array       # [chunk] i32
    hazard_val: jax.Array       # [chunk] i32
    hazard_live: jax.Array      # [chunk] bool
    cursor: jax.Array           # scalar i32 - scan position in old table
    rebuilding: jax.Array       # scalar bool
    epoch: jax.Array            # scalar i32
    lookups: jax.Array          # scalar i32 - queries sampled by
                                # lookup_counted since the last policy
                                # action / epoch swap (probe telemetry)
    expensive: jax.Array        # scalar i32 - sampled queries whose probe
                                # cost crossed the policy threshold
                                # (small_hash.c expensive_lookup_count)


def _be(d: DHashState) -> backends.BucketBackend:
    """The descriptor every op dispatches through (static registry lookup —
    ``d.backend`` is aux data, so this is free under jit)."""
    return backends.get(d.backend)


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def _make_table(backend: str, capacity: int, seed, **kw):
    """Build an empty backend table sized for ``capacity`` live entries
    (the descriptor's sizing policy)."""
    return backends.get(backend).make(capacity, seed, **kw)


def _fused_default(backend: str) -> bool:
    """Resolve ``fused=None``: the DHASH_FUSED env var (``on``/``1``/``true``)
    turns the Pallas kernels on for every backend whose descriptor carries
    the fused op set — the hook CI's fused=on|off test matrix uses to drive
    the whole suite through the fused paths without touching call sites."""
    flag = os.environ.get("DHASH_FUSED", "off").lower()
    return flag in ("1", "on", "true") and backends.get(backend).fused


def make(backend: str = "linear", capacity: int = 1024, *, chunk: int = 256,
         seed: int = 0, fwd_hazard: bool = False, fused: bool | None = None,
         nres_cap: int | None = None, **kw) -> DHashState:
    be = backends.get(backend)
    if fused is None:
        # fwd_hazard is the alternative (jnp) hazard-resolution strategy; the
        # env default must not silently shadow it with the fused branch
        fused = _fused_default(backend) and not fwd_hazard
    if fused and not be.fused:
        raise ValueError(
            f"fused kernels are not implemented for backend {backend!r}; "
            f"fused-capable: "
            f"{tuple(n for n in backends.names() if backends.get(n).fused)}")
    if fused and chunk > probe.SLAB:
        raise ValueError(f"fused tables take a rebuild chunk of at most "
                         f"{probe.SLAB} entries, got {chunk}")
    if fused and be.tpu_refusal and probe.platform() == "tpu":
        raise ValueError(f"backend {backend!r} has no fused kernels that "
                         f"compile for TPU: {be.tpu_refusal}")
    if nres_cap is None:
        nres_cap = be.nres_cap
    old = be.make(capacity, seed, **kw)
    new = be.make(capacity, seed + 1, **kw)
    # distinct buffers per field (aliased leaves break jit buffer donation)
    return DHashState(backend=backend, chunk=chunk, fwd_hazard=fwd_hazard,
                      fused=fused, nres_cap=nres_cap, old=old, new=new,
                      hazard_key=jnp.zeros((chunk,), I32),
                      hazard_val=jnp.zeros((chunk,), I32),
                      hazard_live=jnp.zeros((chunk,), bool),
                      cursor=jnp.asarray(0, I32), rebuilding=jnp.asarray(False),
                      epoch=jnp.asarray(0, I32),
                      lookups=jnp.asarray(0, I32),
                      expensive=jnp.asarray(0, I32))


# ---------------------------------------------------------------------------
# the ordered check: old -> hazard -> new (Lemma 4.1)
# ---------------------------------------------------------------------------

@jax.named_scope("dhash.hazard")
def _hazard_probe(d: DHashState, keys: jax.Array):
    eq = (keys[:, None] == d.hazard_key[None, :]) & d.hazard_live[None, :]
    found = eq.any(-1)
    val, _ = buckets._argpick(eq, jnp.broadcast_to(d.hazard_val[None, :], eq.shape))
    return found, jnp.where(found, val, 0)


def _slow_lookup(dd: DHashState, keys: jax.Array):
    """Rebuild-epoch lookup body: the full old -> hazard -> new ordered
    check (shared by ``lookup`` and ``lookup_counted``)."""
    be = _be(dd)
    if dd.fused:
        return be.ordered_lookup_fused(
            dd.old, dd.new, dd.hazard_key, dd.hazard_val,
            dd.hazard_live, keys, nres_cap=dd.nres_cap)
    if dd.fwd_hazard and be.lookup_fwd is not None:
        # beyond-paper: the old-table probe already passes over the
        # MIGRATED slots of the in-flight chunk, so the hazard check is
        # a forwarding index, not a second pass (§Perf dhash-service)
        f_old, v_old, _, mig = be.lookup_fwd(dd.old, keys)
        with jax.named_scope("dhash.hazard"):
            base = dd.cursor - dd.chunk
            hz_idx = mig - base
            inwin = (mig >= 0) & (hz_idx >= 0) & (hz_idx < dd.chunk)
            safe = jnp.clip(hz_idx, 0, dd.chunk - 1)
            f_hz = inwin & dd.hazard_live[safe] & (dd.hazard_key[safe] == keys)
            v_hz = dd.hazard_val[safe]
    else:
        f_old, v_old, _ = be.lookup(dd.old, keys)        # (1) old table
        f_hz, v_hz = _hazard_probe(dd, keys)             # (2) rebuild_cur
    f_new, v_new, _ = be.lookup(dd.new, keys)            # (3) new table
    found = f_old | f_hz | f_new
    val = jnp.where(f_old, v_old, jnp.where(f_hz, v_hz, v_new))
    return found, val


@jax.named_scope("dhash.lookup")
def lookup(d: DHashState, keys: jax.Array):
    """Batched lookup honouring the rebuild protocol. Returns (found, vals).

    With ``fused`` both branches run the descriptor's Pallas adapters; the
    rebuild-epoch branch is the backend's single-pass ordered probe: ONE
    argsort + ONE pallas_call cover the whole old -> hazard -> new ordered
    check, with the two-level tile map keeping grown new tables resident."""
    be = _be(d)

    def fast(dd: DHashState, kk):
        if dd.fused:
            return be.lookup_fused(dd.old, kk)
        f, v, _ = be.lookup(dd.old, kk)
        return f, v

    return jax.lax.cond(d.rebuilding, _slow_lookup, fast, d, keys)


@jax.named_scope("dhash.lookup")
def lookup_counted(d: DHashState, keys: jax.Array, *,
                   probe_hi: int = 7):
    """Lookup that also feeds the elastic policy's probe telemetry.
    Returns ``(state', (found, vals))``.

    The steady-state branch runs the backend's loc-emitting probe (the same
    single kernel pass — ``loc`` is an extra output, not an extra pass),
    converts ``loc`` to a probe cost through the descriptor's
    ``probe_cost``, and bumps ``DHashState.lookups`` / ``.expensive``
    (queries whose cost crossed ``probe_hi``, small_hash.c's
    EXPENSIVE_LOOKUP_THRESHOLD).  The rebuild-epoch branch answers through
    the ordered check WITHOUT sampling: the fused ordered probe has no loc
    output, and mid-epoch probe lengths reflect the dying table anyway —
    the policy resets the counters at every action/epoch, so the sample
    window is always steady-state."""
    be = _be(d)

    def fast(dd: DHashState, kk):
        if dd.fused and be.lookup_fused_loc is not None:
            f, v, loc = be.lookup_fused_loc(dd.old, kk)
        else:
            f, v, loc = be.lookup(dd.old, kk)
        cost = be.probe_cost(dd.old, kk, f, loc)
        exp = (f & (cost >= probe_hi)).sum(dtype=I32)
        dd = replace(dd, lookups=dd.lookups + I32(kk.size),
                     expensive=dd.expensive + exp)
        return dd, (f, v)

    def slow(dd: DHashState, kk):
        return dd, _slow_lookup(dd, kk)

    return jax.lax.cond(d.rebuilding, slow, fast, d, keys)


def _ins_table(dd: DHashState, t, kk, vv, mm):
    """Descriptor-dispatched insert (shared by user inserts and hazard
    landing, so a fused state's rebuild landing runs the claim kernel).
    The descriptor's ``insert_fused`` folds any post-insert maintenance —
    a fused chain table re-sorts its arena when the insert pushes the dirty
    tail past the dense-window coverage (cond-gated, free on the clean
    steady state)."""
    be = _be(dd)
    if dd.fused:
        return be.insert_fused(t, kk, vv, mm)
    return be.insert(t, kk, vv, mm)


def _insert_either(d: DHashState):
    """The descriptor's two-table insert hook for ``d``'s op set, or None."""
    be = _be(d)
    return be.insert_either_fused if d.fused else be.insert_either


@jax.named_scope("dhash.insert")
def insert(d: DHashState, keys: jax.Array, vals: jax.Array, mask: jax.Array | None = None):
    """Batched insert (set semantics: ok=False if key already present in the
    *target* table — Alg. 6). Returns (state', ok).

    A backend with an ``insert_either`` hook (linear) runs one claim loop
    over both tables and writes them in place; the others pick the target
    with a ``lax.cond``."""
    if mask is None:
        mask = jnp.ones(keys.shape, bool)
    either = _insert_either(d)
    if either is not None:
        old, new, ok = either(d.old, d.new, d.rebuilding, keys, vals, mask)
        return replace(d, old=old, new=new), ok

    def fast(dd: DHashState):
        t, ok = _ins_table(dd, dd.old, keys, vals, mask)
        return replace(dd, old=t), ok

    def slow(dd: DHashState):
        t, ok = _ins_table(dd, dd.new, keys, vals, mask)
        return replace(dd, new=t), ok

    return jax.lax.cond(d.rebuilding, slow, fast, d)


@jax.named_scope("dhash.delete")
def delete(d: DHashState, keys: jax.Array, mask: jax.Array | None = None):
    """Batched delete honouring the ordered check (Alg. 5). Returns (state', ok).

    With ``fused`` the write path is kernel-backed end to end: the fast
    branch tombstones via the descriptor's location-emitting probe adapter,
    and the rebuild-epoch branch is the backend's single-pass
    ``ordered_delete_fused`` — ONE argsort + ONE pallas_call whose
    slot/hazard-index outputs drive the old tombstone, the hazard kill, and
    the new tombstone."""
    if mask is None:
        mask = jnp.ones(keys.shape, bool)
    be = _be(d)

    def _del(dd: DHashState, t, kk, mm):
        if dd.fused:
            return be.delete_fused(t, kk, mm)
        return be.delete(t, kk, mm)

    def fast(dd: DHashState):
        t, ok = _del(dd, dd.old, keys, mask)
        return replace(dd, old=t), ok

    def slow_fused(dd: DHashState):
        os_, ns_, hl, ok = be.ordered_delete_fused(
            dd.old, dd.new, dd.hazard_key, dd.hazard_val, dd.hazard_live,
            keys, mask, nres_cap=dd.nres_cap)
        return replace(dd, old=be.with_state(dd.old, os_),
                       new=be.with_state(dd.new, ns_), hazard_live=hl), ok

    def slow(dd: DHashState):
        if dd.fused:
            return slow_fused(dd)
        t_old, ok_old = _del(dd, dd.old, keys, mask)                   # (1) old
        pending = mask & ~ok_old
        # (2) hazard buffer: clear the live bit (LOGICALLY_REMOVED on the
        # in-flight node) - landing will drop it.
        with jax.named_scope("dhash.hazard"):
            eq = ((keys[:, None] == dd.hazard_key[None, :])
                  & dd.hazard_live[None, :])
            hit_hz = eq.any(-1) & pending
            win_hz = buckets.batch_winners(keys, hit_hz) & hit_hz
            kill = (eq & win_hz[:, None]).any(0)
            hazard_live = dd.hazard_live & ~kill
        pending2 = pending & ~hit_hz
        t_new, ok_new = _del(dd, dd.new, keys, pending2)               # (3) new
        ok = ok_old | win_hz | ok_new
        return replace(dd, old=t_old, new=t_new, hazard_live=hazard_live), ok

    return jax.lax.cond(d.rebuilding, slow, fast, d)


# ---------------------------------------------------------------------------
# rebuild protocol
# ---------------------------------------------------------------------------

def rebuild_start(d: DHashState, new_table=None, *, seed: int | None = None) -> DHashState:
    """Host-level: begin a rebuild into ``new_table`` (fresh hash function).

    Caller contract (paper's rebuild_lock): no rebuild may be in progress.
    """
    be = _be(d)
    if new_table is None:
        if seed is None:
            seed = int(np.random.default_rng().integers(1 << 31))
        new_table = be.fresh_like(d.old, seed)
    if d.fused and be.freeze_old is not None:
        # pre-epoch maintenance hook (chain: freeze the old arena fully
        # sorted and tombstone-reclaimed before the cursor scan starts — the
        # old side stays dirt-free for the whole epoch since inserts target
        # the new table).  Safe exactly here: the cursor resets to 0, so
        # node movement cannot skip the scan.
        d = replace(d, old=be.freeze_old(d.old))
    return replace(d, new=new_table, cursor=jnp.asarray(0, I32),
                   rebuilding=jnp.asarray(True))


def rebuild_extract(d: DHashState) -> DHashState:
    """Pull the next chunk out of the old table into the hazard buffer.

    No-op unless rebuilding with an empty hazard buffer.  With ``fused`` the
    scan is the extract kernel (one pallas_call over the resident slab
    window + one MIGRATED scatter; hazard entries compacted on-device)
    instead of the jnp gather scan."""
    return _extract(d, d.rebuilding & ~d.hazard_live.any())


def _extract(d: DHashState, can: jax.Array) -> DHashState:
    """``rebuild_extract`` where the scalar ``can`` holds."""
    be = _be(d)

    def go(dd: DHashState):
        if dd.fused:
            t, hk, hv, hl, cur = be.extract_chunk_fused(dd.old, dd.cursor,
                                                        dd.chunk)
        else:
            t, hk, hv, hl, cur = be.extract_chunk(dd.old, dd.cursor,
                                                  dd.chunk)
        return replace(dd, old=t, hazard_key=hk, hazard_val=hv,
                       hazard_live=hl, cursor=cur)

    return jax.lax.cond(can, go, lambda dd: dd, d)


def rebuild_land(d: DHashState) -> DHashState:
    """Insert hazard entries into the new table; duplicates lose to the copy
    already in the new table (Alg. 3 lines 34-36); entries killed while in
    hazard (delete during the hazard period) are dropped.

    With ``fused`` the landing runs through the SAME claim kernel as user
    inserts, so the extract and the landing of a whole rebuild epoch stay
    on-device inside the jitted engine step.

    A landing insert can fail two ways and they MUST be told apart: the key
    is already in the new table (a user re-inserted it during the hazard
    window — the new copy wins, drop the hazard entry), or the new table
    had no slot within the probe bound (a burst filling the target
    mid-migration — the hazard entry is the ONLY copy of an acknowledged
    insert, so it stays live and the next transition retries).  The
    disambiguating presence check is the plain jnp probe — elementwise, no
    extra sort or kernel pass — and cond-gated so clean landings never pay
    it."""
    return jax.lax.cond(d.rebuilding, _land, lambda dd: dd, d)


def _land(d: DHashState, live: jax.Array | None = None) -> DHashState:
    """Land the ``live`` hazard entries (all live ones by default) in the
    new table; the others keep their live bit."""
    if live is None:
        live = d.hazard_live
    t, ok = _ins_table(d, d.new, d.hazard_key, d.hazard_val, live)
    failed = live & ~ok

    def reconcile(args):
        t_, failed_ = args
        present, _, _ = _be(d).lookup(t_, d.hazard_key)
        return failed_ & ~present          # keep only the capacity fails

    keep = jax.lax.cond(failed.any(), reconcile,
                        lambda args: jnp.zeros_like(failed), (t, failed))
    return replace(d, new=t, hazard_live=(d.hazard_live & ~live) | keep)


def rebuild_chunk(d: DHashState) -> DHashState:
    """extract + land in one transition (hazard window not externally visible).
    Engines that want the observable hazard period call the two halves."""
    return rebuild_land(rebuild_extract(d))


def rebuild_done(d: DHashState) -> jax.Array:
    """Scalar bool: all chunks migrated and landed."""
    return d.rebuilding & (d.cursor >= _be(d).capacity_of(d.old)) \
        & ~d.hazard_live.any()


def rebuild_finish(d: DHashState) -> DHashState:
    """Host-level epoch swap (Alg. 3 lines 41-46). old/new may differ in
    static shape, so this is not jittable in general; O(1) pytree shuffle
    after one device read that checks ``rebuild_done``."""
    assert bool(jax.device_get(rebuild_done(d))), "rebuild not complete"
    return epoch_swap(d)


def epoch_swap(d: DHashState) -> DHashState:
    """``rebuild_finish`` for a caller that has read ``rebuild_done`` on the
    host itself (the engine's poll): the swap alone, with no device sync."""
    # probe telemetry is per-table-generation: a fresh epoch samples afresh
    return replace(d, old=d.new, new=d.old, cursor=jnp.asarray(0, I32),
                   rebuilding=jnp.asarray(False), epoch=d.epoch + 1,
                   lookups=jnp.asarray(0, I32), expensive=jnp.asarray(0, I32))


@jax.named_scope("dhash.finish_same_shape")
def finish_same_shape(d: DHashState) -> DHashState:
    """Fully-jitted epoch swap, valid when old/new share static shapes
    (table stacks, the policy engine, router rebalancing).  It selects
    every table array whether or not the epoch ends: whole-table passes on
    every call, which ``DHashEngine`` avoids by swapping at its poll."""
    done = rebuild_done(d)
    old_leaves, treedef = jax.tree_util.tree_flatten(d.old)
    new_leaves = jax.tree_util.tree_leaves(d.new)
    sw_old = [jnp.where(done, n, o) for o, n in zip(old_leaves, new_leaves)]
    sw_new = [jnp.where(done, o, n) for o, n in zip(old_leaves, new_leaves)]
    return replace(d,
                   old=jax.tree_util.tree_unflatten(treedef, sw_old),
                   new=jax.tree_util.tree_unflatten(treedef, sw_new),
                   cursor=jnp.where(done, 0, d.cursor).astype(I32),
                   rebuilding=d.rebuilding & ~done,
                   epoch=d.epoch + done.astype(I32),
                   lookups=jnp.where(done, 0, d.lookups).astype(I32),
                   expensive=jnp.where(done, 0, d.expensive).astype(I32))


@jax.named_scope("dhash.rebuild_step")
def rebuild_step(d: DHashState) -> DHashState:
    """One rebuild transition per call: land if hazard pending, else extract.
    Interleave with op batches for concurrent-rebuild execution.

    A backend with an ``insert_either`` hook (linear) lands outside any
    conditional: its claim loop runs no round on an empty mask, so the
    landing runs every step with the mask empty unless it is due, and only
    the extract keeps its ``lax.cond``."""
    if _insert_either(d) is None:
        return jax.lax.cond(d.hazard_live.any(), rebuild_land,
                            rebuild_extract, d)
    pending = d.hazard_live.any()
    landed = _land(d, d.hazard_live & d.rebuilding)
    return _extract(landed, d.rebuilding & ~pending)


@jax.named_scope("dhash.rebuild_autostart")
def rebuild_autostart(d: DHashState) -> DHashState:
    """Fully-jitted rebuild start: when NOT rebuilding, clear the (drained)
    standby table, reseed its hash function on-device from the epoch counter
    (hashing.reseed — no host RNG), and raise ``rebuilding``.

    This is the device-side replacement for the host-level
    ``rebuild_start`` (stack engines, the policy engine): combined with
    ``finish_same_shape`` their steady state never leaves the accelerator.
    Valid when old/new share static shapes (same-capacity rebuilds)."""
    be = _be(d)

    def go(dd: DHashState):
        new = be.clear(dd.new)
        new = be.reseed(new, dd.epoch + 1)
        old = dd.old
        if dd.fused and be.freeze_old is not None:
            # same pre-epoch maintenance as the host-level rebuild_start:
            # sort + reclaim once per epoch, before the cursor scan begins
            old = be.freeze_old(old)
        return replace(dd, old=old, new=new, cursor=jnp.asarray(0, I32),
                       rebuilding=jnp.asarray(True))

    return jax.lax.cond(d.rebuilding, lambda dd: dd, go, d)


# ---------------------------------------------------------------------------
# convenience drivers
# ---------------------------------------------------------------------------

def rebuild_all(d: DHashState, *, finish: bool = True) -> DHashState:
    """Run a complete rebuild to quiescence (host loop; used by tests/benches
    that don't care about interleaving)."""
    cap = _be(d).capacity_of(d.old)
    steps = -(-cap // d.chunk) + 1  # +1 in case a hazard chunk is already pending
    chunk_fn = jax.jit(rebuild_chunk)
    done_fn = jax.jit(rebuild_done)
    for _ in range(steps):
        if bool(jax.device_get(done_fn(d))):
            break
        d = chunk_fn(d)
    return rebuild_finish(d) if finish else d


def count_items(d: DHashState) -> jax.Array:
    be = _be(d)
    return (be.count_live(d.old) + be.count_live(d.new)
            + d.hazard_live.sum(dtype=I32))


# ---------------------------------------------------------------------------
# table stacks: T independent tables batched over a leading axis
# ---------------------------------------------------------------------------
#
# A stack is an ordinary DHashState whose every array leaf carries a leading
# [T] axis (the static meta — backend, chunk, fused, nres_cap — is shared).
# The stack_* ops are jax.vmap over the single-table ops, so T tables cost
# ONE kernel launch per op (the fused 1-sort/1-pallas_call budget holds per
# table step) and each table runs its own rebuild epoch — the multi-tenant
# seam serving/kvcache.py builds per-tenant page tables on.

def make_stack(n_tables: int, backend: str = "linear", capacity: int = 1024,
               *, chunk: int = 256, seed: int = 0, **kw) -> DHashState:
    """Build ``n_tables`` independent tables (decorrelated hash seeds)
    stacked on a leading [T] axis.  All static metadata is shared — that is
    what makes the stack one uniform pytree ``jax.vmap`` can batch."""
    if n_tables < 1:
        raise ValueError(f"need at least one table, got {n_tables}")
    tables = [make(backend, capacity, chunk=chunk, seed=seed + i, **kw)
              for i in range(n_tables)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *tables)


def stack_size(d: DHashState) -> int:
    """Static T of a stacked state (the leading axis of any scalar leaf)."""
    return d.cursor.shape[0]


def unstack(d: DHashState) -> list[DHashState]:
    """Split a stack back into its T independent single-table states."""
    return [jax.tree_util.tree_map(lambda x: x[i], d)
            for i in range(stack_size(d))]


def stack_lookup(d: DHashState, keys: jax.Array,
                 mask: jax.Array | None = None):
    """Batched lookup over the stack: keys [T, Q] -> (found, vals) [T, Q].

    ``mask`` ([T, Q] bool) squelches ``found`` for padding slots — the
    routed entry point: capped send buffers (core/distributed.py,
    serving/kvcache.py) zero-pad each owner's segment, and a zero padding
    key must never report a hit even if some table legitimately holds key
    0.  The vmapped kernel launch is unchanged (mask is applied to the
    result, not the probe)."""
    found, vals = jax.vmap(lookup)(d, keys)
    if mask is not None:
        found = found & mask
    return found, vals


def stack_insert(d: DHashState, keys: jax.Array, vals: jax.Array,
                 mask: jax.Array | None = None):
    """Batched insert over the stack ([T, Q] operands). Returns (state', ok)."""
    if mask is None:
        mask = jnp.ones(keys.shape, bool)
    return jax.vmap(insert)(d, keys, vals, mask)


def stack_delete(d: DHashState, keys: jax.Array,
                 mask: jax.Array | None = None):
    """Batched delete over the stack ([T, Q] operands). Returns (state', ok)."""
    if mask is None:
        mask = jnp.ones(keys.shape, bool)
    return jax.vmap(delete)(d, keys, mask)


def stack_rebuild_step(d: DHashState) -> DHashState:
    """One rebuild transition on every (rebuilding) table of the stack —
    epochs advance independently; idle tables are untouched."""
    return jax.vmap(rebuild_step)(d)


def stack_finish_same_shape(d: DHashState) -> DHashState:
    """Per-table jitted epoch swap: each table swaps exactly when ITS
    rebuild completes (staggered epochs across the stack)."""
    return jax.vmap(finish_same_shape)(d)


def stack_autostart(d: DHashState, start: jax.Array | None = None) -> DHashState:
    """Begin a rebuild on the tables selected by ``start`` [T] bool (all by
    default); tables already rebuilding are untouched.  Fully jitted — the
    per-tenant analogue of ``rebuild_autostart``."""
    if start is None:
        start = jnp.ones((stack_size(d),), bool)

    def one(dd, s):
        return jax.lax.cond(s, rebuild_autostart, lambda x: x, dd)

    return jax.vmap(one)(d, start)


def stack_rebuild_done(d: DHashState) -> jax.Array:
    """[T] bool: which tables have a completed-but-unswapped rebuild."""
    return jax.vmap(rebuild_done)(d)


def stack_count_items(d: DHashState) -> jax.Array:
    """[T] i32: live entries per table (old + new + hazard)."""
    return jax.vmap(count_items)(d)
