"""Modular bucket backends (the paper's pluggable "set algorithms", §3 goal 2).

The paper chains nodes in lock-free linked lists; pointer chasing is hostile
to TPUs, so each backend here is an *array-native* reformulation with the same
observable set semantics:

* ``linear``    — open-addressing, linear probing.  The TPU-native default:
                  bounded vectorized probe sequences, no pointers at all.
* ``twochoice`` — bucketed 2-choice hashing (cuckoo family without eviction):
                  exactly two vector-width bucket reads per lookup.
* ``cuckoo``    — two-table multilevel double hashing with bounded kick-out:
                  the twochoice layout split into two hash-function sides,
                  plus insert-side relocation bounded by ``max_kick`` — the
                  worst-case-bounded lookup backend (probe depth <= lane
                  width even under a collision attack).
* ``chain``     — arena-based chained buckets: the faithful analogue of the
                  paper's Michael-list buckets (insert-at-head, logical
                  deletion via state tags, deferred physical reclamation).
                  jnp traversal is lock-step across the query batch: one
                  gather per hop, bounded by ``max_chain``.  The FUSED path
                  never chases pointers: the arena is kept bucket-sorted
                  and tombstone-compacted (``chain_compact_fused``), so
                  probes are per-bucket ``(start, len)`` segment windows —
                  the same slab reductions as the other backends — with a
                  dense-window dirty tail for post-compaction inserts.

Slot/node states mirror the paper's two flag bits:
  LIVE                ~ reachable node
  TOMB                ~ LOGICALLY_REMOVED      (delete; reclaim deferred)
  MIGRATED            ~ IS_BEING_DISTRIBUTED   (rebuild pulled it into hazard)

All operations are *batched*: a batch of Q independent operations is the SPMD
analogue of Q concurrent threads.  Intra-batch conflicts are resolved
deterministically (lowest original index wins), which is one legal
linearization of the paper's concurrent execution.

Every backend exposes:
  make(...) -> Table
  lookup(t, keys)                -> (found[Q], vals[Q], loc[Q])
  insert(t, keys, vals, mask)    -> (t', ok[Q])     # ok=False if present/full
  delete(t, keys, mask)          -> (t', ok[Q])
  extract_chunk(t, cursor, n)    -> (t', hkeys, hvals, hlive, new_cursor)
  count_live(t) -> scalar
  capacity_of(t) -> int (static)

This module holds the table pytrees and the plain jnp reference ops.  The
Pallas-kernel (``*_fused``) adapters and the per-backend dispatch both live
in ``core/backend.py``: one frozen ``BucketBackend`` descriptor per backend
bundles constructors, plain/fused/ordered op callables, and layout caps —
the generic facades at the bottom of this file dispatch through that
registry, keyed on the table type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hashing
from repro.core.struct_utils import pytree_dataclass, replace
from repro.kernels import window

I32 = jnp.int32
EMPTY, LIVE, TOMB, MIGRATED = I32(0), I32(1), I32(2), I32(3)

BACKENDS = ("linear", "twochoice", "chain", "cuckoo")


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def batch_winners(keys: jax.Array, mask: jax.Array) -> jax.Array:
    """First masked occurrence of each distinct key wins (deterministic
    linearization of intra-batch duplicate ops)."""
    q = keys.shape[0]
    idx = jnp.arange(q, dtype=I32)
    order = jnp.lexsort((idx, (~mask).astype(I32), keys))
    ks, ms = keys[order], mask[order]
    first = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    win_sorted = ms & first
    return jnp.zeros((q,), bool).at[order].set(win_sorted)


def _argpick(hit: jax.Array, vals: jax.Array, axis: int = -1):
    """Select value at the first True along axis (undefined if none)."""
    i = jnp.argmax(hit, axis=axis)
    return jnp.take_along_axis(vals, i[..., None], axis=axis)[..., 0], i


# ---------------------------------------------------------------------------
# linear: open addressing with linear probing
# ---------------------------------------------------------------------------

@pytree_dataclass(meta_fields=("capacity", "max_probes"))
class LinearTable:
    capacity: int
    max_probes: int
    hfn: hashing.HashFn
    key: jax.Array    # [C] i32
    val: jax.Array    # [C] i32
    state: jax.Array  # [C] i32 (EMPTY/LIVE/TOMB/MIGRATED)


def linear_make(capacity: int, hfn: hashing.HashFn, max_probes: int = 64) -> LinearTable:
    # distinct buffers per field (aliased leaves break jit buffer donation)
    def z():
        return jnp.zeros((capacity,), I32)
    return LinearTable(capacity=capacity, max_probes=max_probes, hfn=hfn,
                       key=z(), val=z(), state=z())


def linear_lookup(t: LinearTable, keys: jax.Array):
    found, val, loc, _ = linear_lookup_fwd(t, keys)
    return found, val, loc


def linear_lookup_fwd(t: LinearTable, keys: jax.Array):
    """Lookup that ALSO reports a MIGRATED-slot key match ("tombstone
    forwarding"): a slot whose entry was pulled into the rebuild's hazard
    buffer still holds its key, so the probe that passes over it identifies
    the hazard entry at zero extra cost — the beyond-paper replacement for
    the O(Q x chunk) hazard broadcast compare.  The probe runs over each
    query's gathered window (``kernels/window.py``) as plain XLA.
    Returns (found, val, loc, mig_loc) with mig_loc = -1 if none."""
    c = t.capacity
    h0 = hashing.bucket_of(t.hfn, keys, c)
    kw, sw = window.windows((t.key, t.state), h0, c, t.max_probes)
    qk = keys[:, None]
    hit, stop, pos, inwin = window.probe(kw, sw, (h0 % window.LANES)[:, None],
                                         qk, t.max_probes)
    w = kw.shape[1]
    before = pos < jnp.minimum(stop, jnp.where(hit >= 0, hit, w))
    mig = window.first(inwin & before & (sw == MIGRATED) & (kw == qk), pos, w)
    hit, mig = hit[:, 0], mig[:, 0]
    found = hit >= 0
    loc = jnp.where(found, window.slot(h0, hit) % c, -1)
    mig_loc = jnp.where(mig < w, window.slot(h0, mig) % c, -1)
    return found, jnp.where(found, t.val[loc], 0), loc, mig_loc


def linear_insert(t: LinearTable, keys: jax.Array, vals: jax.Array, mask: jax.Array):
    """Batched insert: ``window.insert`` (claim-first-non-LIVE in the probe
    range, contested slots settled by probe distance then batch index,
    losers claim again) with its claim pass evaluated by XLA."""
    winner = batch_winners(keys, mask)
    h0 = hashing.bucket_of(t.hfn, keys, t.capacity)
    key, val, state, done = window.insert(t.key, t.val, t.state, h0, keys,
                                          vals, winner, t.max_probes)
    return replace(t, key=key, val=val, state=state), done


def linear_delete(t: LinearTable, keys: jax.Array, mask: jax.Array):
    winner = batch_winners(keys, mask)
    found, _, loc = linear_lookup(t, keys)
    ok = winner & found
    wloc = jnp.where(ok, loc, t.capacity)
    state = t.state.at[wloc].set(TOMB, mode="drop")
    return LinearTable(capacity=t.capacity, max_probes=t.max_probes, hfn=t.hfn,
                       key=t.key, val=t.val, state=state), ok


def linear_extract_chunk(t: LinearTable, cursor: jax.Array, n: int):
    pos = cursor + jnp.arange(n, dtype=I32)
    valid = pos < t.capacity
    cpos = jnp.where(valid, pos, 0)
    live = valid & (t.state[cpos] == LIVE)
    hkeys = jnp.where(live, t.key[cpos], 0)
    hvals = jnp.where(live, t.val[cpos], 0)
    state = t.state.at[jnp.where(live, cpos, t.capacity)].set(MIGRATED, mode="drop")
    new_cursor = jnp.minimum(cursor + n, t.capacity)
    t = LinearTable(capacity=t.capacity, max_probes=t.max_probes, hfn=t.hfn,
                    key=t.key, val=t.val, state=state)
    return t, hkeys, hvals, live, new_cursor


def linear_count_live(t: LinearTable):
    return jnp.sum(t.state == LIVE)


def linear_clear(t: LinearTable) -> LinearTable:
    z = jnp.zeros((t.capacity,), I32)
    return LinearTable(capacity=t.capacity, max_probes=t.max_probes, hfn=t.hfn,
                       key=z, val=z, state=z)


# ---------------------------------------------------------------------------
# twochoice: bucketed 2-choice hashing (W-wide vector buckets)
# ---------------------------------------------------------------------------

@pytree_dataclass(meta_fields=("nbuckets", "width", "max_rounds"))
class TwoChoiceTable:
    nbuckets: int
    width: int
    max_rounds: int
    hfn_a: hashing.HashFn
    hfn_b: hashing.HashFn
    key: jax.Array    # [B, W] i32
    val: jax.Array    # [B, W] i32
    state: jax.Array  # [B, W] i32


def twochoice_make(nbuckets: int, hfn_a: hashing.HashFn, hfn_b: hashing.HashFn,
                   width: int = 8, max_rounds: int = 8) -> TwoChoiceTable:
    def z():
        return jnp.zeros((nbuckets, width), I32)
    return TwoChoiceTable(nbuckets=nbuckets, width=width, max_rounds=max_rounds,
                          hfn_a=hfn_a, hfn_b=hfn_b, key=z(), val=z(), state=z())


def _tc_rows(t: TwoChoiceTable, keys: jax.Array):
    ba = hashing.bucket_of(t.hfn_a, keys, t.nbuckets)
    bb = hashing.bucket_of(t.hfn_b, keys, t.nbuckets)
    return ba, bb


def twochoice_lookup(t: TwoChoiceTable, keys: jax.Array):
    ba, bb = _tc_rows(t, keys)
    hit_a = (t.key[ba] == keys[:, None]) & (t.state[ba] == LIVE)   # [Q, W]
    hit_b = (t.key[bb] == keys[:, None]) & (t.state[bb] == LIVE)
    fa, fb = hit_a.any(-1), hit_b.any(-1)
    va, sa = _argpick(hit_a, t.val[ba])
    vb, sb = _argpick(hit_b, t.val[bb])
    found = fa | fb
    val = jnp.where(fa, va, vb)
    loc = jnp.where(fa, ba * t.width + sa, jnp.where(fb, bb * t.width + sb, -1))
    return found, val, loc


def twochoice_insert(t: TwoChoiceTable, keys: jax.Array, vals: jax.Array, mask: jax.Array):
    b, w, q = t.nbuckets, t.width, keys.shape[0]
    winner = batch_winners(keys, mask)
    present, _, _ = twochoice_lookup(t, keys)
    pending0 = winner & ~present
    ba, bb = _tc_rows(t, keys)
    idx = jnp.arange(q, dtype=I32)
    nslots = b * w

    def body(r, carry):
        key, val, state, pending, done = carry
        bkt = jnp.where(r % 2 == 0, ba, bb)
        row_free = state[bkt] != LIVE                       # [Q, W]
        has_free = pending & row_free.any(-1)
        slot = jnp.argmax(row_free, axis=-1)
        flat = bkt * w + slot
        wflat = jnp.where(has_free, flat, nslots)
        claim = jnp.full((nslots,), q, I32).at[wflat].min(idx, mode="drop")
        won = has_free & (claim[flat % nslots] == idx) & (wflat < nslots)
        wp = jnp.where(won, flat, nslots)
        key = key.reshape(-1).at[wp].set(keys, mode="drop").reshape(b, w)
        val = val.reshape(-1).at[wp].set(vals, mode="drop").reshape(b, w)
        state = state.reshape(-1).at[wp].set(LIVE, mode="drop").reshape(b, w)
        done = done | won
        pending = pending & ~won
        return key, val, state, pending, done

    init = (t.key, t.val, t.state, pending0, jnp.zeros((q,), bool))
    key, val, state, _, done = jax.lax.fori_loop(0, t.max_rounds, body, init)
    t = TwoChoiceTable(nbuckets=b, width=w, max_rounds=t.max_rounds,
                       hfn_a=t.hfn_a, hfn_b=t.hfn_b, key=key, val=val, state=state)
    return t, done


def twochoice_delete(t: TwoChoiceTable, keys: jax.Array, mask: jax.Array):
    winner = batch_winners(keys, mask)
    found, _, loc = twochoice_lookup(t, keys)
    ok = winner & found
    wloc = jnp.where(ok, loc, t.nbuckets * t.width)
    state = t.state.reshape(-1).at[wloc].set(TOMB, mode="drop").reshape(t.nbuckets, t.width)
    return TwoChoiceTable(nbuckets=t.nbuckets, width=t.width, max_rounds=t.max_rounds,
                          hfn_a=t.hfn_a, hfn_b=t.hfn_b, key=t.key, val=t.val, state=state), ok


def twochoice_extract_chunk(t: TwoChoiceTable, cursor: jax.Array, n: int):
    nslots = t.nbuckets * t.width
    pos = cursor + jnp.arange(n, dtype=I32)
    valid = pos < nslots
    cpos = jnp.where(valid, pos, 0)
    ks, vs, ss = t.key.reshape(-1), t.val.reshape(-1), t.state.reshape(-1)
    live = valid & (ss[cpos] == LIVE)
    hkeys = jnp.where(live, ks[cpos], 0)
    hvals = jnp.where(live, vs[cpos], 0)
    ss = ss.at[jnp.where(live, cpos, nslots)].set(MIGRATED, mode="drop")
    new_cursor = jnp.minimum(cursor + n, nslots)
    t = TwoChoiceTable(nbuckets=t.nbuckets, width=t.width, max_rounds=t.max_rounds,
                       hfn_a=t.hfn_a, hfn_b=t.hfn_b, key=t.key, val=t.val,
                       state=ss.reshape(t.nbuckets, t.width))
    return t, hkeys, hvals, live, new_cursor


def twochoice_count_live(t: TwoChoiceTable):
    return jnp.sum(t.state == LIVE)


def twochoice_clear(t: TwoChoiceTable) -> TwoChoiceTable:
    z = jnp.zeros((t.nbuckets, t.width), I32)
    return TwoChoiceTable(nbuckets=t.nbuckets, width=t.width,
                          max_rounds=t.max_rounds, hfn_a=t.hfn_a,
                          hfn_b=t.hfn_b, key=z, val=z, state=z)


# ---------------------------------------------------------------------------
# cuckoo: two-table multilevel double hashing with bounded kick-out
# ---------------------------------------------------------------------------
#
# The worst-case-bounded backend ("Cascade hash tables" in PAPERS.md;
# MAX_KICK_OUT/HASH_FUNC_NUM in SNIPPETS.md snippet 1): one [2B, W] slot
# array split into side A (rows [0, B), addressed by hfn_a) and side B
# (rows [B, 2B), addressed by hfn_b).  A key lives in exactly one of its two
# candidate rows, so EVERY lookup is two W-wide row gathers — probe depth is
# bounded by the lane width no matter how adversarial the key set, which is
# the defense DURING a collision attack (bench_attack.py); the insert-side
# relocation is bounded by ``max_kick`` (kernels/ref.py::cuckoo_kick_ref).
# Because the candidate rows are plain row indices, the fused path reuses
# the twochoice row-gather kernels VERBATIM with side-offset rows — same
# 1-sort/1-pallas_call budget, nothing new to lower.

@pytree_dataclass(meta_fields=("nbuckets", "width", "max_kick"))
class CuckooTable:
    nbuckets: int     # rows PER SIDE: the slot arrays are [2 * nbuckets, W]
    width: int
    max_kick: int     # bounded kick-out iterations (insert relocation)
    hfn_a: hashing.HashFn
    hfn_b: hashing.HashFn
    key: jax.Array    # [2B, W] i32
    val: jax.Array    # [2B, W] i32
    state: jax.Array  # [2B, W] i32


def cuckoo_make(nbuckets: int, hfn_a: hashing.HashFn, hfn_b: hashing.HashFn,
                width: int = 8, max_kick: int = 32) -> CuckooTable:
    def z():
        return jnp.zeros((2 * nbuckets, width), I32)
    return CuckooTable(nbuckets=nbuckets, width=width, max_kick=max_kick,
                       hfn_a=hfn_a, hfn_b=hfn_b, key=z(), val=z(), state=z())


def _ck_rows(t: CuckooTable, keys: jax.Array):
    """The two candidate rows of each key, side-offset into the [2B, W]
    array: a-rows in [0, B), b-rows in [B, 2B).  Disjoint row ranges are
    what let every twochoice row-indexed op drive this table unchanged."""
    ra = hashing.bucket_of(t.hfn_a, keys, t.nbuckets)
    rb = t.nbuckets + hashing.bucket_of(t.hfn_b, keys, t.nbuckets)
    return ra, rb


def cuckoo_lookup(t: CuckooTable, keys: jax.Array):
    ra, rb = _ck_rows(t, keys)
    hit_a = (t.key[ra] == keys[:, None]) & (t.state[ra] == LIVE)   # [Q, W]
    hit_b = (t.key[rb] == keys[:, None]) & (t.state[rb] == LIVE)
    fa, fb = hit_a.any(-1), hit_b.any(-1)
    va, sa = _argpick(hit_a, t.val[ra])
    vb, sb = _argpick(hit_b, t.val[rb])
    found = fa | fb
    val = jnp.where(fa, va, vb)
    loc = jnp.where(fa, ra * t.width + sa, jnp.where(fb, rb * t.width + sb, -1))
    return found, val, loc


def cuckoo_insert(t: CuckooTable, keys: jax.Array, vals: jax.Array, mask: jax.Array):
    """Set-semantic insert: the bounded kick-out loop (plan-A free-lane
    claim / plan-B victim relocation, per-row arbitration) IS the whole
    placement — its first iterations are exactly the twochoice direct
    claims, and only genuinely contended rows pay relocation iterations.
    ok=False iff present or the kick budget exhausts (no resident is ever
    displaced without a landing slot)."""
    from repro.kernels import ref
    winner = batch_winners(keys, mask)
    present, _, _ = cuckoo_lookup(t, keys)
    pending = winner & ~present
    ra, rb = _ck_rows(t, keys)

    def kick(op):
        k, v, s, done0 = op
        k2, v2, s2, done = ref.cuckoo_kick_ref(
            k, v, s, ra, rb, t.hfn_a, t.hfn_b, t.nbuckets,
            keys, vals, pending, t.max_kick)
        return k2, v2, s2, done0 | done

    key, val, state, done = jax.lax.cond(
        pending.any(), kick, lambda op: op,
        (t.key, t.val, t.state, jnp.zeros(keys.shape, bool)))
    return replace(t, key=key, val=val, state=state), done


def cuckoo_delete(t: CuckooTable, keys: jax.Array, mask: jax.Array):
    winner = batch_winners(keys, mask)
    found, _, loc = cuckoo_lookup(t, keys)
    ok = winner & found
    nslots = 2 * t.nbuckets * t.width
    wloc = jnp.where(ok, loc, nslots)
    state = t.state.reshape(-1).at[wloc].set(TOMB, mode="drop").reshape(
        2 * t.nbuckets, t.width)
    return replace(t, state=state), ok


def cuckoo_extract_chunk(t: CuckooTable, cursor: jax.Array, n: int):
    nslots = 2 * t.nbuckets * t.width
    pos = cursor + jnp.arange(n, dtype=I32)
    valid = pos < nslots
    cpos = jnp.where(valid, pos, 0)
    ks, vs, ss = t.key.reshape(-1), t.val.reshape(-1), t.state.reshape(-1)
    live = valid & (ss[cpos] == LIVE)
    hkeys = jnp.where(live, ks[cpos], 0)
    hvals = jnp.where(live, vs[cpos], 0)
    ss = ss.at[jnp.where(live, cpos, nslots)].set(MIGRATED, mode="drop")
    new_cursor = jnp.minimum(cursor + n, nslots)
    return replace(t, state=ss.reshape(2 * t.nbuckets, t.width)), \
        hkeys, hvals, live, new_cursor


def cuckoo_count_live(t: CuckooTable):
    return jnp.sum(t.state == LIVE)


def cuckoo_clear(t: CuckooTable) -> CuckooTable:
    z = jnp.zeros((2 * t.nbuckets, t.width), I32)
    return replace(t, key=z, val=z, state=z)


# ---------------------------------------------------------------------------
# chain: arena-based chained buckets (paper-faithful Michael-list analogue)
# ---------------------------------------------------------------------------

@pytree_dataclass(meta_fields=("nbuckets", "arena", "max_chain", "dirty_cap"))
class ChainTable:
    nbuckets: int
    arena: int        # node capacity N
    max_chain: int    # traversal bound (>= max expected chain incl. tombstones)
    dirty_cap: int    # dense-window budget for the post-compaction dirty
                      # tail (the fused path's coverage bound; the
                      # ``BucketBackend`` descriptor supplies the default)
    hfn: hashing.HashFn
    akey: jax.Array   # [N] i32
    aval: jax.Array   # [N] i32
    anext: jax.Array  # [N] i32 (-1 terminates)
    astate: jax.Array # [N] i32
    heads: jax.Array  # [B] i32 (-1 empty)
    free_stack: jax.Array  # [N] i32 - free node indices live at [0, free_top)
    free_top: jax.Array    # scalar i32
    # arena-sorted layout metadata (the fused path's view of the same arena):
    # [0, sorted_upto) holds the bucket-sorted, tombstone-compacted segments
    # (bucket b's nodes at [bstart[b], bstart[b]+blen[b])), and nodes
    # allocated SINCE the last compaction occupy the contiguous "dirty" tail
    # [sorted_upto, arena - free_top).  ``chain_dirty(t)`` derives the dirty
    # count; ``chain_compact_fused`` restores dirty == 0.
    bstart: jax.Array      # [B] i32 - sorted-segment start per bucket
    blen: jax.Array        # [B] i32 - sorted-segment length per bucket
    sorted_upto: jax.Array # scalar i32 - arena prefix in bucket-sorted order


def chain_make(nbuckets: int, arena: int, hfn: hashing.HashFn,
               max_chain: int = 64, dirty_cap: int | None = None) -> ChainTable:
    n = arena
    if dirty_cap is None:
        # resolve from the chain descriptor (core/backend.py) so tables
        # built directly through chain_make agree with registry-built ones
        # — the descriptor field is the single source of truth for the cap
        from repro.core import backend
        dirty_cap = backend.get("chain").dirty_cap
    # free_stack is DESCENDING so pops allocate ascending positions: the
    # allocated region is always the contiguous prefix [0, n - free_top),
    # which is what keeps the fused path's dirty tail a dense window.
    return ChainTable(
        nbuckets=nbuckets, arena=n, max_chain=max_chain, dirty_cap=dirty_cap,
        hfn=hfn,
        akey=jnp.zeros((n,), I32), aval=jnp.zeros((n,), I32),
        anext=jnp.full((n,), -1, I32), astate=jnp.zeros((n,), I32),
        heads=jnp.full((nbuckets,), -1, I32),
        free_stack=n - 1 - jnp.arange(n, dtype=I32),
        free_top=jnp.asarray(n, I32),
        bstart=jnp.zeros((nbuckets,), I32), blen=jnp.zeros((nbuckets,), I32),
        sorted_upto=jnp.asarray(0, I32))


def chain_dirty(t: ChainTable) -> jax.Array:
    """Scalar i32: nodes allocated since the last compaction (they live at
    [sorted_upto, arena - free_top) — allocation is always a prefix)."""
    return t.arena - t.free_top - t.sorted_upto


def chain_lookup(t: ChainTable, keys: jax.Array, bucket: jax.Array | None = None):
    """Lock-step batched traversal with DYNAMIC termination: the step cost is
    the longest still-active chain in the batch, not the static bound — so
    collision attacks show up in wall time exactly as they do on the paper's
    pointer-chasing implementations."""
    q = keys.shape[0]
    b = hashing.bucket_of(t.hfn, keys, t.nbuckets) if bucket is None else bucket
    cur0 = t.heads[b]

    def cond(carry):
        cur, found, _, _, fuel = carry
        return ((cur >= 0) & ~found).any() & (fuel > 0)

    def body(carry):
        cur, found, val, loc, fuel = carry
        valid = cur >= 0
        c = jnp.where(valid, cur, 0)
        hit = valid & (t.astate[c] == LIVE) & (t.akey[c] == keys) & ~found
        val = jnp.where(hit, t.aval[c], val)
        loc = jnp.where(hit, cur, loc)
        found = found | hit
        step = valid & ~found
        cur = jnp.where(step, t.anext[c], jnp.where(found, cur, -1))
        return cur, found, val, loc, fuel - 1

    init = (cur0, jnp.zeros((q,), bool), jnp.zeros((q,), I32),
            jnp.full((q,), -1, I32), jnp.asarray(t.max_chain, I32))
    _, found, val, loc, _ = jax.lax.while_loop(cond, body, init)
    return found, val, loc


def _chain_link(t: ChainTable, keys, node, can, bucket: jax.Array | None = None):
    """Insert nodes ``node`` (where can) at the heads of their buckets,
    preserving original-index order within each bucket group."""
    q = keys.shape[0]
    b = hashing.bucket_of(t.hfn, keys, t.nbuckets) if bucket is None else bucket
    sortkey = jnp.where(can, b, t.nbuckets)
    idx = jnp.arange(q, dtype=I32)
    order = jnp.lexsort((idx, sortkey))
    sb, snode, scan = sortkey[order], node[order], can[order]
    nxt_same = jnp.concatenate([snode[1:], jnp.full((1,), -1, I32)])
    same_bucket = jnp.concatenate([sb[1:] == sb[:-1], jnp.zeros((1,), bool)])
    old_head = t.heads[jnp.where(scan, sb, 0)]
    nxt = jnp.where(same_bucket, nxt_same, jnp.where(scan, old_head, -1))
    anext = t.anext.at[jnp.where(scan, snode, t.arena)].set(nxt, mode="drop")
    is_start = jnp.concatenate([jnp.ones((1,), bool), sb[1:] != sb[:-1]])
    heads = t.heads.at[jnp.where(scan & is_start, sb, t.nbuckets)].set(snode, mode="drop")
    return anext, heads


def chain_insert(t: ChainTable, keys: jax.Array, vals: jax.Array, mask: jax.Array,
                 bucket: jax.Array | None = None):
    q, n = keys.shape[0], t.arena
    winner = batch_winners(keys, mask)
    present, _, _ = chain_lookup(t, keys, bucket)
    want = winner & ~present
    rank = jnp.cumsum(want.astype(I32)) - 1
    can = want & (rank < t.free_top)
    node = t.free_stack[jnp.where(can, t.free_top - 1 - rank, 0)]
    wnode = jnp.where(can, node, n)
    akey = t.akey.at[wnode].set(keys, mode="drop")
    aval = t.aval.at[wnode].set(vals, mode="drop")
    astate = t.astate.at[wnode].set(LIVE, mode="drop")
    t1 = replace(t, akey=akey, aval=aval, astate=astate)
    anext, heads = _chain_link(t1, keys, node, can, bucket)
    free_used = jnp.sum(can.astype(I32))
    # new nodes extend the dirty tail; the sorted segments are untouched
    t2 = replace(t1, anext=anext, heads=heads,
                 free_top=t.free_top - free_used)
    return t2, can


def chain_delete(t: ChainTable, keys: jax.Array, mask: jax.Array,
                 bucket: jax.Array | None = None):
    winner = batch_winners(keys, mask)
    found, _, loc = chain_lookup(t, keys, bucket)
    ok = winner & found
    wloc = jnp.where(ok, loc, t.arena)
    astate = t.astate.at[wloc].set(TOMB, mode="drop")
    return replace(t, astate=astate), ok


def chain_extract_chunk(t: ChainTable, cursor: jax.Array, n: int):
    pos = cursor + jnp.arange(n, dtype=I32)
    valid = pos < t.arena
    cpos = jnp.where(valid, pos, 0)
    live = valid & (t.astate[cpos] == LIVE)
    hkeys = jnp.where(live, t.akey[cpos], 0)
    hvals = jnp.where(live, t.aval[cpos], 0)
    astate = t.astate.at[jnp.where(live, cpos, t.arena)].set(MIGRATED, mode="drop")
    new_cursor = jnp.minimum(cursor + n, t.arena)
    return replace(t, astate=astate), hkeys, hvals, live, new_cursor


def chain_compact(t: ChainTable) -> ChainTable:
    """Physically reclaim tombstones: rebuild all chains from live nodes.

    The paper defers physical unlinking to later traversals / call_rcu; the
    batched analogue is a periodic vectorized compaction (also doubles as the
    post-rebuild reclamation of the old arena)."""
    live = t.astate == LIVE
    fresh = chain_make(t.nbuckets, t.arena, t.hfn, t.max_chain, t.dirty_cap)
    t2, _ = chain_insert(fresh, jnp.where(live, t.akey, 0), t.aval, live)
    return t2


def chain_count_live(t: ChainTable):
    return jnp.sum(t.astate == LIVE)


def chain_clear(t: ChainTable) -> ChainTable:
    n = t.arena
    return replace(
        t, akey=jnp.zeros((n,), I32), aval=jnp.zeros((n,), I32),
        anext=jnp.full((n,), -1, I32), astate=jnp.zeros((n,), I32),
        heads=jnp.full((t.nbuckets,), -1, I32),
        free_stack=n - 1 - jnp.arange(n, dtype=I32),
        free_top=jnp.asarray(n, I32),
        bstart=jnp.zeros((t.nbuckets,), I32),
        blen=jnp.zeros((t.nbuckets,), I32),
        sorted_upto=jnp.asarray(0, I32))


# -- The Pallas-accelerated (``*_fused``) chain paths moved to
# core/backend.py with every other backend's fused adapters: the arena-
# sorted layout itself (and its jnp maintenance) stays here ----------------

def _chain_parts(t: ChainTable):
    """The raw-array views the chain ops consume: arena triple, link pair
    (for the pointer-chasing fallback), segment quad."""
    return ((t.akey, t.aval, t.astate), (t.anext, t.heads),
            (t.bstart, t.blen, t.sorted_upto, chain_dirty(t)))


# ---------------------------------------------------------------------------
# dispatch facade: generic table-typed entry points over the descriptor
# registry (core/backend.py) — the jnp ops above are what the registry
# binds; these facades are for callers holding a bare table pytree
# ---------------------------------------------------------------------------

def _be(t):
    from repro.core import backend
    return backend.of_table(t)


def backend_of(table) -> str:
    """Registry name of a table pytree ("linear"/"twochoice"/"chain"/...)."""
    return _be(table).name


def lookup(t, keys):
    return _be(t).lookup(t, keys)


def insert(t, keys, vals, mask):
    return _be(t).insert(t, keys, vals, mask)


def delete(t, keys, mask):
    return _be(t).delete(t, keys, mask)


def extract_chunk(t, cursor, n):
    return _be(t).extract_chunk(t, cursor, n)


def count_live(t):
    return _be(t).count_live(t)


def clear(t):
    """Empty the table in place (shape/hash-function preserving, jittable) —
    the on-device reset of a drained table before it becomes the next rebuild
    target."""
    return _be(t).clear(t)


def capacity_of(t) -> int:
    return _be(t).capacity_of(t)


# Legacy import surface: the fused adapters lived here before the
# descriptor-protocol refactor collapsed them into core/backend.py.
_MOVED_TO_BACKEND = (
    "linear_lookup_fused", "linear_insert_fused", "linear_delete_fused",
    "linear_extract_chunk_fused",
    "twochoice_lookup_fused", "twochoice_insert_fused",
    "twochoice_delete_fused", "twochoice_ordered_lookup_fused",
    "twochoice_ordered_delete_fused", "twochoice_extract_chunk_fused",
    "cuckoo_lookup_fused", "cuckoo_insert_fused", "cuckoo_delete_fused",
    "cuckoo_ordered_lookup_fused", "cuckoo_ordered_delete_fused",
    "cuckoo_extract_chunk_fused",
    "chain_lookup_fused", "chain_insert_fused", "chain_delete_fused",
    "chain_ordered_lookup_fused", "chain_ordered_delete_fused",
    "chain_extract_chunk_fused", "chain_compact_fused",
    "chain_maybe_compact",
)


def __getattr__(name: str):
    if name in _MOVED_TO_BACKEND:
        from repro.core import backend
        return getattr(backend, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
