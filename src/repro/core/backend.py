"""BucketBackend descriptor protocol: ONE registry entry per backend.

The paper's headline modularity claim ("DHash ... allows programmers to
select a variety of lock-free/wait-free set algorithms as the implementation
of hash table buckets") lives here.  A backend is a frozen ``BucketBackend``
descriptor bundling everything the DHash layer needs to drive it:

* its table constructor and sizing policy (``make``), the same-geometry
  rebuild-target constructor (``fresh_like``), and the on-device hash
  refresh (``reseed``);
* the plain jnp op set (``lookup``/``insert``/``delete``/``extract_chunk``/
  ``count_live``/``clear`` — the oracle surface, always present);
* the fused Pallas op set (``*_fused`` + the rebuild-epoch
  ``ordered_lookup_fused``/``ordered_delete_fused`` — ``None`` when the
  backend has no kernel path);
* layout metadata: ``nres_cap`` (resident new-table blocks of the two-level
  tile map, see kernels/ops.py) and ``dirty_cap`` (the chain arena's
  dense-window dirty-tail budget), promoted from kernels/ops.py module
  constants to descriptor fields and threaded through ``dhash.make()``;
* optional hooks: ``freeze_old`` (pre-epoch maintenance — the chain arena
  compaction), ``lookup_fwd`` (the linear backend's MIGRATED-slot hazard
  forwarding), ``insert_either``/``insert_either_fused`` (the linear
  backend's insert into old or new as one claim loop over both tables).

``core/dhash.py`` contains ZERO per-backend branches: every public op
dispatches through the descriptor looked up by ``DHashState.backend``.
Because the descriptor holds all statics, every backend's table state is a
uniform pytree — which is what makes ``dhash.make_stack`` + ``jax.vmap``
batching over a leading table axis possible (multi-tenant serving).

Adding a backend is one ``register()`` call: implement the jnp op set over a
pytree table class, optionally the fused adapters over kernels/ops.py, and
nothing in dhash/engine/distributed/serving changes.

The ``*_fused`` adapters in this module are the thin descriptor-bound glue
over ``kernels/ops.py`` (hash the keys, call the op, reassemble the table
pytree) that previously lived as per-backend wrapper triplets in
``core/buckets.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import buckets, hashing
from repro.core.buckets import (ChainTable, CuckooTable, LinearTable,
                                TwoChoiceTable, _chain_parts, _ck_rows,
                                _tc_rows, batch_winners, chain_dirty)
from repro.core.struct_utils import replace
# The registry entries below need the cap values at registration time.
from repro.kernels.probe import DIRTY_CAP, NRES_CAP


# ---------------------------------------------------------------------------
# the descriptor
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BucketBackend:
    """Registry entry: everything DHash needs to drive one bucket backend.

    Uniform call surface (``t`` is the backend's table pytree):

      make(capacity, seed, **kw) -> t          empty table sized for capacity
      fresh_like(t, seed) -> t'                empty same-geometry table with
                                               fresh hash function(s) (host)
      reseed(t, salt) -> t'                    on-device hash refresh
      capacity_of(t) -> int                    static scan-order capacity
      with_state(t, state') -> t'              reattach a slot/node state
                                               array (ordered-delete landing)
      lookup(t, keys) -> (found, vals, loc)
      insert(t, keys, vals, mask) -> (t', ok)
      delete(t, keys, mask) -> (t', ok)
      extract_chunk(t, cursor, n) -> (t', hkeys, hvals, hlive, cursor')
      count_live(t) -> scalar
      count_tomb(t) -> scalar                  tombstoned slots/nodes (the
                                               elastic policy's reclaim
                                               trigger, core/policy.py)
      clear(t) -> t'
      probe_cost(t, keys, found, loc) -> i32[Q]  probe-length cost of each
                                               hit, from the loc output of
                                               the backend's lookup (probe
                                               telemetry for the policy's
                                               expensive-lookup counter)
      slots_for(capacity) -> int               slot count make(capacity)
                                               would allocate (host-side
                                               resize planning; None =
                                               derive by building a table)

    Fused set (``None`` = no kernel path; all-or-none per backend):

      lookup_fused(t, keys) -> (found, vals)
      lookup_fused_loc(t, keys) -> (found, vals, loc)   the same single
                                               kernel pass with its loc
                                               output kept (probe
                                               telemetry; no extra pass)
      insert_fused(t, keys, vals, mask) -> (t', ok)   folds the backend's
                                               post-insert maintenance (chain
                                               re-sorts past its dirty_cap)
      delete_fused(t, keys, mask) -> (t', ok)
      extract_chunk_fused(t, cursor, n) -> like extract_chunk
      ordered_lookup_fused(t_old, t_new, hk, hv, hl, keys, *, nres_cap)
          -> (found, vals)                     whole Lemma-4.1 ordered check
      ordered_delete_fused(t_old, t_new, hk, hv, hl, keys, mask, *, nres_cap)
          -> (old_state', new_state', hl', ok)

    Two-table insert hooks (``None`` = ``dhash.insert`` picks the table
    with a ``lax.cond``):

      insert_either(t_old, t_new, to_new, keys, vals, mask)
          -> (t_old', t_new', ok)              ``insert`` into t_new where
                                               the scalar to_new holds, else
                                               into t_old, writing both in
                                               place
      insert_either_fused(...)                 the same on the fused op set
    """

    name: str
    table_cls: type
    # layout caps: descriptor-held defaults, threaded through dhash.make()
    # (nres_cap lands on DHashState, dirty_cap on the chain table itself)
    nres_cap: int
    dirty_cap: int
    # construction & maintenance
    make: Callable[..., Any]
    fresh_like: Callable[..., Any]
    reseed: Callable[..., Any]
    capacity_of: Callable[[Any], int]
    with_state: Callable[..., Any]
    # plain jnp ops (the oracle surface)
    lookup: Callable[..., Any]
    insert: Callable[..., Any]
    delete: Callable[..., Any]
    extract_chunk: Callable[..., Any]
    count_live: Callable[..., Any]
    clear: Callable[..., Any]
    # occupancy / probe telemetry (elastic policy inputs, core/policy.py)
    count_tomb: Callable[..., Any] = None
    probe_cost: Callable[..., Any] = None
    slots_for: Callable[[int], int] | None = None
    # True for backends whose placement can fail below physical capacity
    # (twochoice row pairs, cuckoo kick exhaustion): the elastic policy's
    # in-place mode holds same-shape rehashes until the load drains below
    # its placement headroom, so a rehash can never park unplaceable keys
    # in the hazard buffer indefinitely (core/policy.py)
    bounded_placement: bool = False
    # fused kernel ops
    lookup_fused: Callable[..., Any] | None = None
    lookup_fused_loc: Callable[..., Any] | None = None
    insert_fused: Callable[..., Any] | None = None
    delete_fused: Callable[..., Any] | None = None
    extract_chunk_fused: Callable[..., Any] | None = None
    ordered_lookup_fused: Callable[..., Any] | None = None
    ordered_delete_fused: Callable[..., Any] | None = None
    # optional hooks
    freeze_old: Callable[..., Any] | None = None
    lookup_fwd: Callable[..., Any] | None = None
    insert_either: Callable[..., Any] | None = None
    insert_either_fused: Callable[..., Any] | None = None
    # the TPU compiler's refusal of this backend's fused kernels (kernel and
    # error, as tests/test_tpu_compile.py records it), None when they
    # compile; dhash.make raises it instead of building a fused table on a
    # TPU
    tpu_refusal: str | None = None

    @property
    def fused(self) -> bool:
        """True iff this backend has the full fused kernel op set."""
        return self.lookup_fused is not None

    def __post_init__(self):
        fused_set = (self.lookup_fused, self.lookup_fused_loc,
                     self.insert_fused, self.delete_fused,
                     self.extract_chunk_fused, self.ordered_lookup_fused,
                     self.ordered_delete_fused)
        have = [f is not None for f in fused_set]
        if any(have) and not all(have):
            raise ValueError(f"backend {self.name!r}: fused ops must be "
                             f"all-or-none, got {have}")


REGISTRY: dict[str, BucketBackend] = {}


def register(be: BucketBackend) -> BucketBackend:
    """Add a descriptor to the registry (last registration wins, so a user
    backend may shadow a built-in)."""
    REGISTRY[be.name] = be
    return be


def get(name: str) -> BucketBackend:
    try:
        return REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{tuple(REGISTRY)}") from None


def names() -> tuple[str, ...]:
    return tuple(REGISTRY)


def of_table(t) -> BucketBackend:
    """Descriptor for a table pytree instance (type-keyed reverse lookup)."""
    for be in REGISTRY.values():
        if isinstance(t, be.table_cls):
            return be
    raise TypeError(f"no registered backend for table type {type(t)!r}")


# ---------------------------------------------------------------------------
# linear: fused adapters (kernels/ops.py probe/claim/extract kernels)
# ---------------------------------------------------------------------------

def linear_lookup_fused(t: LinearTable, keys: jax.Array):
    """Kernel-backed lookup.  Returns (found, vals)."""
    from repro.kernels import ops
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    return ops.probe_lookup(t.key, t.val, t.state, h0, keys,
                            max_probes=t.max_probes)


def linear_lookup_fused_loc(t: LinearTable, keys: jax.Array):
    """Kernel-backed lookup keeping the kernel's loc output: the SAME single
    pallas_call as ``linear_lookup_fused``, returning (found, vals, loc) for
    probe telemetry (core/policy.py)."""
    from repro.kernels import ops
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    return ops.probe_lookup(t.key, t.val, t.state, h0, keys,
                            max_probes=t.max_probes, with_loc=True)


def linear_insert_fused(t: LinearTable, keys: jax.Array, vals: jax.Array,
                        mask: jax.Array):
    """Kernel-backed insert: batch_winners dedup (the kernel's caller
    contract), then one claim pass + one claim-settling sort + one
    scatter."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    tk, tv, ts, ok = ops.probe_insert(t.key, t.val, t.state, h0, keys, vals,
                                      winner, max_probes=t.max_probes)
    return replace(t, key=tk, val=tv, state=ts), ok


def linear_insert_either_fused(t_old: LinearTable, t_new: LinearTable,
                               to_new: jax.Array, keys: jax.Array,
                               vals: jax.Array, mask: jax.Array):
    """Kernel-backed ``buckets.linear_insert_either``: the claim kernel
    reads the target table, one claim loop writes both.  Returns (t_old',
    t_new', ok)."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    h0 = jnp.where(to_new, hashing.bucket_of(t_new.hfn, keys, t_new.capacity),
                   hashing.bucket_of(t_old.hfn, keys, t_old.capacity))
    a, b, ok = ops.probe_insert_either(
        (t_old.key, t_old.val, t_old.state),
        (t_new.key, t_new.val, t_new.state), to_new, h0, keys, vals, winner,
        max_probes=(t_old.max_probes, t_new.max_probes))
    return (replace(t_old, key=a[0], val=a[1], state=a[2]),
            replace(t_new, key=b[0], val=b[1], state=b[2]), ok)


def linear_delete_fused(t: LinearTable, keys: jax.Array, mask: jax.Array):
    """Kernel-backed delete: the lookup kernel's hit slot is tombstoned in
    ONE pass (one pallas_call + one scatter)."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    state, ok = ops.probe_delete(t.key, t.val, t.state, h0, keys, winner,
                                 max_probes=t.max_probes)
    return replace(t, state=state), ok


def linear_extract_chunk_fused(t: LinearTable, cursor: jax.Array, n: int):
    """Kernel-backed rebuild chunk scan: one pallas_call over the chunk +
    one MIGRATED scatter; hazard entries come back COMPACTED (live entries
    first) — identical as a set, which is all the hazard protocol
    observes."""
    from repro.kernels import ops
    state, hk, hv, hl, cur = ops.extract_chunk_fused(
        t.key, t.val, t.state, cursor, chunk=n)
    return replace(t, state=state), hk, hv, hl, cur


def linear_ordered_lookup_fused(t_old: LinearTable, t_new: LinearTable,
                                hazard_key: jax.Array, hazard_val: jax.Array,
                                hazard_live: jax.Array, keys: jax.Array, *,
                                nres_cap: int = NRES_CAP):
    """Kernel-backed rebuild-epoch lookup: the whole ordered check
    (old -> hazard -> new, Lemma 4.1) in ONE probe2 pallas_call, for any
    old/new table sizes (``nres_cap`` sizes the sorted-slab backends' tile
    map; the windowed linear kernels have none).  Returns (found, vals)."""
    from repro.kernels import ops
    del nres_cap
    h0_old = hashing.bucket_of(t_old.hfn, keys, t_old.capacity)
    h0_new = hashing.bucket_of(t_new.hfn, keys, t_new.capacity)
    return ops.ordered_lookup_fused(
        (t_old.key, t_old.val, t_old.state),
        (t_new.key, t_new.val, t_new.state),
        hazard_key, hazard_val, hazard_live, h0_old, h0_new, keys,
        max_probes=t_old.max_probes)


def linear_ordered_delete_fused(t_old: LinearTable, t_new: LinearTable,
                                hazard_key: jax.Array, hazard_val: jax.Array,
                                hazard_live: jax.Array, keys: jax.Array,
                                mask: jax.Array, *, nres_cap: int = NRES_CAP):
    """Kernel-backed rebuild-epoch delete (paper Alg. 5): the SAME single
    probe2 pass resolves old-slot / hazard-index / new-slot; three scatters
    land the result.  Returns (old_state', new_state', hazard_live', ok)."""
    from repro.kernels import ops
    del nres_cap
    winner = batch_winners(keys, mask)
    h0_old = hashing.bucket_of(t_old.hfn, keys, t_old.capacity)
    h0_new = hashing.bucket_of(t_new.hfn, keys, t_new.capacity)
    return ops.ordered_delete_fused(
        (t_old.key, t_old.val, t_old.state),
        (t_new.key, t_new.val, t_new.state),
        hazard_key, hazard_val, hazard_live, h0_old, h0_new, keys, winner,
        max_probes=t_old.max_probes)


# ---------------------------------------------------------------------------
# twochoice: fused adapters (2Q-entry one-sort row-gather kernels)
# ---------------------------------------------------------------------------

def twochoice_lookup_fused(t: TwoChoiceTable, keys: jax.Array):
    """Kernel-backed 2-choice lookup.  Returns (found, vals, loc) — the same
    triple as ``buckets.twochoice_lookup`` so the delete path can reuse
    ``loc``."""
    from repro.kernels import ops
    ba, bb = _tc_rows(t, keys)
    return ops.twochoice_lookup(t.key, t.val, t.state, ba, bb, keys)


def twochoice_insert_fused(t: TwoChoiceTable, keys: jax.Array,
                           vals: jax.Array, mask: jax.Array):
    """Kernel-backed 2-choice insert: batch_winners dedup, then one claim
    pass + one scatter (a-row claims shadow b-row claims of the same
    query)."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    ba, bb = _tc_rows(t, keys)
    tk, tv, ts, ok = ops.twochoice_insert(t.key, t.val, t.state, ba, bb,
                                          keys, vals, winner,
                                          max_rounds=t.max_rounds)
    return replace(t, key=tk, val=tv, state=ts), ok


def twochoice_delete_fused(t: TwoChoiceTable, keys: jax.Array,
                           mask: jax.Array):
    """Kernel-backed 2-choice delete: reuses the fused lookup's location
    output — one kernel pass + one tombstone scatter."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    ba, bb = _tc_rows(t, keys)
    state, ok = ops.twochoice_delete(t.key, t.val, t.state, ba, bb, keys,
                                     winner)
    return replace(t, state=state), ok


def twochoice_ordered_lookup_fused(t_old: TwoChoiceTable,
                                   t_new: TwoChoiceTable,
                                   hazard_key: jax.Array,
                                   hazard_val: jax.Array,
                                   hazard_live: jax.Array,
                                   keys: jax.Array, *,
                                   nres_cap: int = NRES_CAP):
    """Kernel-backed twochoice rebuild-epoch lookup: the whole ordered check
    in ONE argsort + ONE tc_probe2 pallas_call.  Returns (found, vals)."""
    from repro.kernels import ops
    ba_o, bb_o = _tc_rows(t_old, keys)
    ba_n, bb_n = _tc_rows(t_new, keys)
    return ops.twochoice_ordered_lookup(
        (t_old.key, t_old.val, t_old.state),
        (t_new.key, t_new.val, t_new.state),
        hazard_key, hazard_val, hazard_live,
        ba_o, bb_o, ba_n, bb_n, keys, nres_cap=nres_cap)


def twochoice_ordered_delete_fused(t_old: TwoChoiceTable,
                                   t_new: TwoChoiceTable,
                                   hazard_key: jax.Array,
                                   hazard_val: jax.Array,
                                   hazard_live: jax.Array,
                                   keys: jax.Array, mask: jax.Array, *,
                                   nres_cap: int = NRES_CAP):
    """Kernel-backed twochoice rebuild-epoch delete (paper Alg. 5): the SAME
    single tc_probe2 pass resolves old-slot / hazard-index / new-slot.
    Returns the raw (old_state', new_state', hazard_live', ok[Q])."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    ba_o, bb_o = _tc_rows(t_old, keys)
    ba_n, bb_n = _tc_rows(t_new, keys)
    return ops.twochoice_ordered_delete(
        (t_old.key, t_old.val, t_old.state),
        (t_new.key, t_new.val, t_new.state),
        hazard_key, hazard_val, hazard_live,
        ba_o, bb_o, ba_n, bb_n, keys, winner, nres_cap=nres_cap)


def twochoice_extract_chunk_fused(t: TwoChoiceTable, cursor: jax.Array,
                                  n: int):
    """Kernel-backed 2-choice rebuild chunk scan: the extract kernel runs on
    the row-major flattened arrays (the scan order is identical)."""
    from repro.kernels import ops
    state, hk, hv, hl, cur = ops.extract_chunk_fused(
        t.key.reshape(-1), t.val.reshape(-1), t.state.reshape(-1), cursor,
        chunk=n)
    return replace(t, state=state.reshape(t.nbuckets, t.width)), \
        hk, hv, hl, cur


# ---------------------------------------------------------------------------
# cuckoo: fused adapters — the twochoice row-gather kernels verbatim, fed
# side-offset candidate rows (a-rows [0, B), b-rows [B, 2B) of the [2B, W]
# array).  Same ONE sort + ONE pallas_call per op; only the insert grows a
# cond-gated bounded kick-out (pure jnp — zero extra kernel launches)
# ---------------------------------------------------------------------------

def cuckoo_lookup_fused(t: CuckooTable, keys: jax.Array):
    """Kernel-backed cuckoo lookup via the twochoice row-gather kernel over
    side-offset rows.  Returns (found, vals, loc)."""
    from repro.kernels import ops
    ra, rb = _ck_rows(t, keys)
    return ops.twochoice_lookup(t.key, t.val, t.state, ra, rb, keys)


def cuckoo_insert_fused(t: CuckooTable, keys: jax.Array, vals: jax.Array,
                        mask: jax.Array):
    """Kernel-backed cuckoo insert: the twochoice claim kernel places every
    key whose candidate rows have room (max_rounds=2 — one try per side);
    anything still unplaced escapes to the cond-gated bounded kick-out
    (kernels/ref.py::cuckoo_kick_ref) — free when nothing overflows."""
    from repro.kernels import ops, ref
    winner = batch_winners(keys, mask)
    ra, rb = _ck_rows(t, keys)
    tk, tv, ts, ok = ops.twochoice_insert(t.key, t.val, t.state, ra, rb,
                                          keys, vals, winner,
                                          max_rounds=2)
    maybe = winner & ~ok

    def kick(op):
        k, v, s, ok0 = op
        # re-check presence inside the branch (ok=False means present OR
        # both rows full; only the latter may relocate)
        fa, _, _ = ref.tc_row_lookup_ref(k, v, s, ra, keys)
        fb, _, _ = ref.tc_row_lookup_ref(k, v, s, rb, keys)
        pend = maybe & ~(fa | fb)
        k2, v2, s2, done = ref.cuckoo_kick_ref(
            k, v, s, ra, rb, t.hfn_a, t.hfn_b, t.nbuckets,
            keys, vals, pend, t.max_kick)
        return k2, v2, s2, ok0 | done

    tk, tv, ts, ok = jax.lax.cond(maybe.any(), kick, lambda op: op,
                                  (tk, tv, ts, ok))
    return replace(t, key=tk, val=tv, state=ts), ok


def cuckoo_delete_fused(t: CuckooTable, keys: jax.Array, mask: jax.Array):
    """Kernel-backed cuckoo delete: the twochoice location-emitting pass +
    one tombstone scatter."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    ra, rb = _ck_rows(t, keys)
    state, ok = ops.twochoice_delete(t.key, t.val, t.state, ra, rb, keys,
                                     winner)
    return replace(t, state=state), ok


def cuckoo_ordered_lookup_fused(t_old: CuckooTable, t_new: CuckooTable,
                                hazard_key: jax.Array, hazard_val: jax.Array,
                                hazard_live: jax.Array, keys: jax.Array, *,
                                nres_cap: int = NRES_CAP):
    """Kernel-backed cuckoo rebuild-epoch lookup: the twochoice tc_probe2
    pass (ONE argsort + ONE pallas_call) over side-offset rows."""
    from repro.kernels import ops
    ra_o, rb_o = _ck_rows(t_old, keys)
    ra_n, rb_n = _ck_rows(t_new, keys)
    return ops.twochoice_ordered_lookup(
        (t_old.key, t_old.val, t_old.state),
        (t_new.key, t_new.val, t_new.state),
        hazard_key, hazard_val, hazard_live,
        ra_o, rb_o, ra_n, rb_n, keys, nres_cap=nres_cap)


def cuckoo_ordered_delete_fused(t_old: CuckooTable, t_new: CuckooTable,
                                hazard_key: jax.Array, hazard_val: jax.Array,
                                hazard_live: jax.Array, keys: jax.Array,
                                mask: jax.Array, *, nres_cap: int = NRES_CAP):
    """Kernel-backed cuckoo rebuild-epoch delete (paper Alg. 5) via the
    twochoice probe2 pass over side-offset rows.  Returns the raw
    (old_state', new_state', hazard_live', ok[Q])."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    ra_o, rb_o = _ck_rows(t_old, keys)
    ra_n, rb_n = _ck_rows(t_new, keys)
    return ops.twochoice_ordered_delete(
        (t_old.key, t_old.val, t_old.state),
        (t_new.key, t_new.val, t_new.state),
        hazard_key, hazard_val, hazard_live,
        ra_o, rb_o, ra_n, rb_n, keys, winner, nres_cap=nres_cap)


def cuckoo_extract_chunk_fused(t: CuckooTable, cursor: jax.Array, n: int):
    """Kernel-backed cuckoo rebuild chunk scan on the row-major flattened
    [2B*W] arrays (the scan order is identical)."""
    from repro.kernels import ops
    state, hk, hv, hl, cur = ops.extract_chunk_fused(
        t.key.reshape(-1), t.val.reshape(-1), t.state.reshape(-1), cursor,
        chunk=n)
    return replace(t, state=state.reshape(2 * t.nbuckets, t.width)), \
        hk, hv, hl, cur


# ---------------------------------------------------------------------------
# chain: fused adapters over the arena-sorted node layout
# ---------------------------------------------------------------------------

def chain_lookup_fused(t: ChainTable, keys: jax.Array):
    """Kernel-backed chain lookup over the arena-sorted layout.  Returns
    (found, vals, loc) — ``loc`` is the arena node index (-1 if absent)."""
    from repro.kernels import ops
    b = hashing.bucket_of(t.hfn, keys, t.nbuckets)
    return ops.chain_lookup_fused(*_chain_parts(t), b, keys,
                                  max_chain=t.max_chain,
                                  dirty_cap=t.dirty_cap)


def chain_insert_fused(t: ChainTable, keys: jax.Array, vals: jax.Array,
                       mask: jax.Array):
    """Kernel-backed chain insert: batch_winners dedup, ONE sort keyed on
    the bucket, one presence pallas_call, then vectorized tail allocation +
    segmented head relink — no pointer chasing.  New nodes extend the dirty
    tail; ``chain_maybe_compact`` restores the sorted invariant."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    b = hashing.bucket_of(t.hfn, keys, t.nbuckets)
    arena_t, links, seg = _chain_parts(t)
    akey, aval, astate, anext, heads, free_top, ok = ops.chain_insert_fused(
        arena_t, links, seg, t.free_stack, t.free_top, b, keys, vals, winner,
        max_chain=t.max_chain, dirty_cap=t.dirty_cap)
    return replace(t, akey=akey, aval=aval, astate=astate, anext=anext,
                   heads=heads, free_top=free_top), ok


def chain_delete_fused(t: ChainTable, keys: jax.Array, mask: jax.Array):
    """Kernel-backed chain delete: the location-emitting probe (sorted
    segment window + dirty-tail compare) tombstones in ONE pass."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    b = hashing.bucket_of(t.hfn, keys, t.nbuckets)
    astate, ok = ops.chain_delete_fused(*_chain_parts(t), b, keys, winner,
                                        max_chain=t.max_chain,
                                        dirty_cap=t.dirty_cap)
    return replace(t, astate=astate), ok


def chain_ordered_lookup_fused(t_old: ChainTable, t_new: ChainTable,
                               hazard_key: jax.Array, hazard_val: jax.Array,
                               hazard_live: jax.Array, keys: jax.Array, *,
                               nres_cap: int = NRES_CAP):
    """Kernel-backed chain rebuild-epoch lookup: the whole ordered check in
    ONE sort + ONE chain_probe2 pallas_call.  Returns (found, vals)."""
    from repro.kernels import ops
    b_old = hashing.bucket_of(t_old.hfn, keys, t_old.nbuckets)
    b_new = hashing.bucket_of(t_new.hfn, keys, t_new.nbuckets)
    return ops.chain_ordered_lookup(
        *_chain_parts(t_old), *_chain_parts(t_new),
        hazard_key, hazard_val, hazard_live, b_old, b_new, keys,
        max_chain=max(t_old.max_chain, t_new.max_chain),
        nres_cap=nres_cap, dirty_cap=max(t_old.dirty_cap, t_new.dirty_cap))


def chain_ordered_delete_fused(t_old: ChainTable, t_new: ChainTable,
                               hazard_key: jax.Array, hazard_val: jax.Array,
                               hazard_live: jax.Array, keys: jax.Array,
                               mask: jax.Array, *, nres_cap: int = NRES_CAP):
    """Kernel-backed chain rebuild-epoch delete (paper Alg. 5).  Returns the
    raw (old_astate', new_astate', hazard_live', ok[Q])."""
    from repro.kernels import ops
    winner = batch_winners(keys, mask)
    b_old = hashing.bucket_of(t_old.hfn, keys, t_old.nbuckets)
    b_new = hashing.bucket_of(t_new.hfn, keys, t_new.nbuckets)
    return ops.chain_ordered_delete(
        *_chain_parts(t_old), *_chain_parts(t_new),
        hazard_key, hazard_val, hazard_live, b_old, b_new, keys, winner,
        max_chain=max(t_old.max_chain, t_new.max_chain),
        nres_cap=nres_cap, dirty_cap=max(t_old.dirty_cap, t_new.dirty_cap))


def chain_extract_chunk_fused(t: ChainTable, cursor: jax.Array, n: int):
    """Kernel-backed rebuild chunk scan: the arena is a flat array, so the
    extract kernel runs verbatim (positions are scan order)."""
    from repro.kernels import ops
    astate, hk, hv, hl, cur = ops.extract_chunk_fused(
        t.akey, t.aval, t.astate, cursor, chunk=n)
    return replace(t, astate=astate), hk, hv, hl, cur


def chain_compact_fused(t: ChainTable) -> ChainTable:
    """Restore the arena-sorted invariant: ONE segmented sort keyed on
    (bucket, arena index) with dead nodes pushed to the end, the compaction
    gather, per-bucket (start, len) offsets, and a vectorized pointer
    rebuild.  Physically reclaims tombstones; dirty count drops to 0."""
    from repro.kernels import ops
    b = hashing.bucket_of(t.hfn, t.akey, t.nbuckets)
    (akey, aval, astate, anext, heads, free_stack, free_top, bstart, blen,
     sorted_upto) = ops.chain_compact_fused(t.akey, t.aval, t.astate, b,
                                            nbuckets=t.nbuckets)
    return replace(t, akey=akey, aval=aval, astate=astate, anext=anext,
                   heads=heads, free_stack=free_stack, free_top=free_top,
                   bstart=bstart, blen=blen, sorted_upto=sorted_upto)


def chain_maybe_compact(t: ChainTable, *,
                        threshold: int | None = None) -> ChainTable:
    """Compaction trigger: re-sort the arena iff the dirty tail has outgrown
    the dense-window coverage (the table's ``dirty_cap`` by default — a
    descriptor field threaded through construction).  cond-gated, so the
    clean steady state never pays the sort."""
    thresh = t.dirty_cap if threshold is None else threshold
    return jax.lax.cond(chain_dirty(t) > thresh, chain_compact_fused,
                        lambda tt: tt, t)


def _chain_insert_fused_compacting(t: ChainTable, keys, vals, mask):
    """The descriptor-bound chain insert: the fused insert plus the
    cond-gated arena re-sort that keeps subsequent probes kernel-resident —
    what the DHash layer (user inserts AND hazard landings) runs."""
    t2, ok = chain_insert_fused(t, keys, vals, mask)
    return chain_maybe_compact(t2), ok


# ---------------------------------------------------------------------------
# construction / maintenance adapters
# ---------------------------------------------------------------------------

def _next_pow2(x: int) -> int:
    return 1 << (int(x) - 1).bit_length()


def _make_linear(capacity: int, seed, *, load_factor: float = 0.75,
                 max_probes: int = 128) -> LinearTable:
    # A probe window is two 128-slot rows whatever the bound up to 129
    # (window.window_rows), so 128 costs no more than 64 did.  At 64, a
    # 2^26-slot table loaded to 0.5 has occupied runs longer than the bound
    # (66 and 72 slots for two of four seeded key sets), and a late insert
    # starting near the front of such a run was refused.
    rng = np.random.default_rng(seed)
    slots = _next_pow2(int(capacity / load_factor) + 1)
    return buckets.linear_make(slots, hashing.fresh("mix32", rng),
                               max_probes=max_probes)


def _make_twochoice(capacity: int, seed, *, load_factor: float = 0.75,
                    bucket_width: int = 8) -> TwoChoiceTable:
    rng = np.random.default_rng(seed)
    nb = _next_pow2(int(capacity / (load_factor * bucket_width)) + 1)
    return buckets.twochoice_make(nb, hashing.fresh("mix32", rng),
                                  hashing.fresh("mix32", rng),
                                  width=bucket_width)


def _make_cuckoo(capacity: int, seed, *, load_factor: float = 0.75,
                 bucket_width: int = 8, max_kick: int = 32) -> CuckooTable:
    rng = np.random.default_rng(seed)
    nb = _next_pow2(int(capacity / (load_factor * 2 * bucket_width)) + 1)
    return buckets.cuckoo_make(nb, hashing.fresh("mix32", rng),
                               hashing.fresh("mix32", rng),
                               width=bucket_width, max_kick=max_kick)


def _make_chain(capacity: int, seed, *, load_factor: float = 0.75,
                max_chain: int = 64, nbuckets: int | None = None,
                dirty_cap: int | None = None) -> ChainTable:
    rng = np.random.default_rng(seed)
    nb = nbuckets if nbuckets is not None else _next_pow2(max(capacity // 16, 1))
    # dirty_cap=None passes through: chain_make resolves it from the
    # registry ("chain" entry), the ONE place that default lives — so a
    # user descriptor shadowing "chain" wins on every construction path
    return buckets.chain_make(nb, capacity, hashing.fresh("mix32", rng),
                              max_chain=max_chain, dirty_cap=dirty_cap)


def _fresh_linear(t: LinearTable, seed) -> LinearTable:
    return buckets.linear_make(t.capacity, hashing.fresh("mix32", seed),
                               t.max_probes)


def _fresh_twochoice(t: TwoChoiceTable, seed) -> TwoChoiceTable:
    rng = np.random.default_rng(seed)
    return buckets.twochoice_make(t.nbuckets, hashing.fresh("mix32", rng),
                                  hashing.fresh("mix32", rng), width=t.width,
                                  max_rounds=t.max_rounds)


def _fresh_cuckoo(t: CuckooTable, seed) -> CuckooTable:
    rng = np.random.default_rng(seed)
    return buckets.cuckoo_make(t.nbuckets, hashing.fresh("mix32", rng),
                               hashing.fresh("mix32", rng), width=t.width,
                               max_kick=t.max_kick)


def _fresh_chain(t: ChainTable, seed) -> ChainTable:
    return buckets.chain_make(t.nbuckets, t.arena,
                              hashing.fresh("mix32", seed),
                              max_chain=t.max_chain, dirty_cap=t.dirty_cap)


def _reseed_one(t, salt: jax.Array):
    return replace(t, hfn=hashing.reseed(t.hfn, salt))


def _reseed_twochoice(t: TwoChoiceTable, salt: jax.Array) -> TwoChoiceTable:
    return replace(t, hfn_a=hashing.reseed(t.hfn_a, salt),
                   hfn_b=hashing.reseed(t.hfn_b, salt + 0x5851F42))


def _reseed_cuckoo(t: CuckooTable, salt: jax.Array) -> CuckooTable:
    return replace(t, hfn_a=hashing.reseed(t.hfn_a, salt),
                   hfn_b=hashing.reseed(t.hfn_b, salt + 0x5851F42))


# ---------------------------------------------------------------------------
# occupancy / probe telemetry (elastic policy inputs)
# ---------------------------------------------------------------------------

def _linear_count_tomb(t: LinearTable) -> jax.Array:
    return (t.state == buckets.TOMB).sum(dtype=jnp.int32)


def _twochoice_count_tomb(t: TwoChoiceTable) -> jax.Array:
    return (t.state == buckets.TOMB).sum(dtype=jnp.int32)


def _cuckoo_count_tomb(t: CuckooTable) -> jax.Array:
    return (t.state == buckets.TOMB).sum(dtype=jnp.int32)


def _chain_count_tomb(t: ChainTable) -> jax.Array:
    return (t.astate == buckets.TOMB).sum(dtype=jnp.int32)


def _linear_probe_cost(t: LinearTable, keys, found, loc) -> jax.Array:
    """Probe distance of each hit.  Works for BOTH loc conventions: the
    plain lookup's wrapped table coordinate and the fused kernel's unwrapped
    padded coordinate (``loc >= h0``) — the mod folds either to the probe
    index."""
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    dist = jnp.mod(loc - h0, t.capacity)
    return jnp.where(found & (loc >= 0), dist, 0).astype(jnp.int32)


def _twochoice_probe_cost(t: TwoChoiceTable, keys, found, loc) -> jax.Array:
    """Cost = lane depth within the hit's row (both the plain and fused
    lookups emit loc = row * width + lane).  Two-choice inserts target the
    LESS loaded of the two candidate rows, so which row hit carries no
    signal — but a hit deep in its row means that row is saturating, the
    clustering symptom the expensive-lookup trigger exists to catch."""
    cost = loc % t.width
    return jnp.where(found & (loc >= 0), cost, 0).astype(jnp.int32)


def _cuckoo_probe_cost(t: CuckooTable, keys, found, loc) -> jax.Array:
    """Cost = lane depth within the hit's row (loc = row * width + lane),
    exactly as for twochoice — and here the depth is also the WORST-CASE
    bound: a key is only ever in one of its two candidate rows, so no
    lookup, adversarial or not, can cost more than ``width - 1``.  This is
    the number ``BENCH_attack.json`` gates as ``attack_probe_bound``."""
    cost = loc % t.width
    return jnp.where(found & (loc >= 0), cost, 0).astype(jnp.int32)


def _chain_probe_cost(t: ChainTable, keys, found, loc) -> jax.Array:
    """Chain depth of the hit: exact offset inside the sorted-arena segment;
    a dirty-tail hit (appended since the last compaction) is charged the
    full chain length + 1 — it IS the end of its chain."""
    b = hashing.bucket_of(t.hfn, keys, t.nbuckets)
    in_sorted = loc < t.sorted_upto
    depth = jnp.where(in_sorted, loc - t.bstart[b], t.blen[b] + 1)
    return jnp.where(found & (loc >= 0), depth, 0).astype(jnp.int32)


def _linear_slots_for(capacity: int) -> int:
    return _next_pow2(int(capacity / 0.75) + 1)          # mirrors _make_linear


def _twochoice_slots_for(capacity: int) -> int:
    return _next_pow2(int(capacity / (0.75 * 8)) + 1) * 8   # _make_twochoice


def _cuckoo_slots_for(capacity: int) -> int:
    return 2 * _next_pow2(int(capacity / (0.75 * 2 * 8)) + 1) * 8  # _make_cuckoo


def _chain_slots_for(capacity: int) -> int:
    return int(capacity)                                 # arena = capacity


def _drop_loc(fn):
    """Normalize a loc-returning lookup to the descriptor's (found, vals)."""
    def wrapped(t, keys, **kw):
        f, v, _loc = fn(t, keys, **kw)
        return f, v
    return wrapped


# ---------------------------------------------------------------------------
# the built-in registry
# ---------------------------------------------------------------------------

# What Mosaic answers when the sorted-slab kernels are compiled for a TPU
# (tests/test_tpu_compile.py holds these as strict xfails): their per-query
# gathers from a VMEM window have no TPU lowering.
_TC_REFUSAL = ("tc_lookup_tiles, tc_insert_tiles and tc_probe2_tiles: "
               "ValueError: Shape mismatch in input, indices and output "
               "(per-entry row gather from the resident row blocks)")
_CHAIN_REFUSAL = ("chain_probe_tiles and chain_probe2_tiles: "
                  "NotImplementedError: Only 2D gather is supported "
                  "(per-query gather from the 1-D slab window)")

LINEAR = register(BucketBackend(
    name="linear",
    table_cls=LinearTable,
    nres_cap=NRES_CAP,
    dirty_cap=0,                       # no deferred-maintenance tail
    make=_make_linear,
    fresh_like=_fresh_linear,
    reseed=_reseed_one,
    capacity_of=lambda t: t.capacity,
    with_state=lambda t, s: replace(t, state=s),
    lookup=buckets.linear_lookup,
    insert=buckets.linear_insert,
    delete=buckets.linear_delete,
    extract_chunk=buckets.linear_extract_chunk,
    count_live=buckets.linear_count_live,
    clear=buckets.linear_clear,
    count_tomb=_linear_count_tomb,
    probe_cost=_linear_probe_cost,
    slots_for=_linear_slots_for,
    lookup_fused=linear_lookup_fused,
    lookup_fused_loc=linear_lookup_fused_loc,
    insert_fused=linear_insert_fused,
    delete_fused=linear_delete_fused,
    extract_chunk_fused=linear_extract_chunk_fused,
    ordered_lookup_fused=linear_ordered_lookup_fused,
    ordered_delete_fused=linear_ordered_delete_fused,
    lookup_fwd=buckets.linear_lookup_fwd,
    insert_either=buckets.linear_insert_either,
    insert_either_fused=linear_insert_either_fused,
))

TWOCHOICE = register(BucketBackend(
    name="twochoice",
    table_cls=TwoChoiceTable,
    nres_cap=NRES_CAP,
    dirty_cap=0,
    make=_make_twochoice,
    fresh_like=_fresh_twochoice,
    reseed=_reseed_twochoice,
    capacity_of=lambda t: t.nbuckets * t.width,
    with_state=lambda t, s: replace(t, state=s),
    lookup=buckets.twochoice_lookup,
    insert=buckets.twochoice_insert,
    delete=buckets.twochoice_delete,
    extract_chunk=buckets.twochoice_extract_chunk,
    count_live=buckets.twochoice_count_live,
    clear=buckets.twochoice_clear,
    count_tomb=_twochoice_count_tomb,
    probe_cost=_twochoice_probe_cost,
    slots_for=_twochoice_slots_for,
    lookup_fused=_drop_loc(twochoice_lookup_fused),
    lookup_fused_loc=twochoice_lookup_fused,
    insert_fused=twochoice_insert_fused,
    delete_fused=twochoice_delete_fused,
    extract_chunk_fused=twochoice_extract_chunk_fused,
    ordered_lookup_fused=twochoice_ordered_lookup_fused,
    ordered_delete_fused=twochoice_ordered_delete_fused,
    bounded_placement=True,
    tpu_refusal=_TC_REFUSAL,
))

CUCKOO = register(BucketBackend(
    name="cuckoo",
    table_cls=CuckooTable,
    nres_cap=NRES_CAP,
    dirty_cap=0,
    make=_make_cuckoo,
    fresh_like=_fresh_cuckoo,
    reseed=_reseed_cuckoo,
    capacity_of=lambda t: 2 * t.nbuckets * t.width,
    with_state=lambda t, s: replace(t, state=s),
    lookup=buckets.cuckoo_lookup,
    insert=buckets.cuckoo_insert,
    delete=buckets.cuckoo_delete,
    extract_chunk=buckets.cuckoo_extract_chunk,
    count_live=buckets.cuckoo_count_live,
    clear=buckets.cuckoo_clear,
    count_tomb=_cuckoo_count_tomb,
    probe_cost=_cuckoo_probe_cost,
    slots_for=_cuckoo_slots_for,
    lookup_fused=_drop_loc(cuckoo_lookup_fused),
    lookup_fused_loc=cuckoo_lookup_fused,
    insert_fused=cuckoo_insert_fused,
    delete_fused=cuckoo_delete_fused,
    extract_chunk_fused=cuckoo_extract_chunk_fused,
    ordered_lookup_fused=cuckoo_ordered_lookup_fused,
    ordered_delete_fused=cuckoo_ordered_delete_fused,
    bounded_placement=True,
    tpu_refusal=_TC_REFUSAL,
))

CHAIN = register(BucketBackend(
    name="chain",
    table_cls=ChainTable,
    nres_cap=NRES_CAP,
    dirty_cap=DIRTY_CAP,
    make=_make_chain,
    fresh_like=_fresh_chain,
    reseed=_reseed_one,
    capacity_of=lambda t: t.arena,
    with_state=lambda t, s: replace(t, astate=s),
    lookup=buckets.chain_lookup,
    insert=buckets.chain_insert,
    delete=buckets.chain_delete,
    extract_chunk=buckets.chain_extract_chunk,
    count_live=buckets.chain_count_live,
    clear=buckets.chain_clear,
    count_tomb=_chain_count_tomb,
    probe_cost=_chain_probe_cost,
    slots_for=_chain_slots_for,
    lookup_fused=_drop_loc(chain_lookup_fused),
    lookup_fused_loc=chain_lookup_fused,
    insert_fused=_chain_insert_fused_compacting,
    delete_fused=chain_delete_fused,
    extract_chunk_fused=chain_extract_chunk_fused,
    ordered_lookup_fused=chain_ordered_lookup_fused,
    ordered_delete_fused=chain_ordered_delete_fused,
    freeze_old=chain_compact_fused,
    tpu_refusal=_CHAIN_REFUSAL,
))
