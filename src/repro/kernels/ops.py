"""Jit'd wrappers around the Pallas kernels: window gathers, claims, fallback.

Linear backend (``kernels/window.py`` windows, ``kernels/probe.py``
kernels): every op gathers each query's probe window with XLA row gathers
and runs ONE pallas_call over the dense windows.

* ``probe_lookup`` / ``probe_delete`` — the lookup kernel; the delete
  tombstones the hit slot with one scatter (no second probe).
* ``ordered_lookup_fused`` / ``ordered_delete_fused`` — the rebuild-epoch
  ops: ONE probe2 pallas_call covers the old table, the hazard buffer and the
  new table (the paper's Lemma-4.1 order), whatever the two tables' sizes.
* ``probe_insert`` — ``window.insert`` with the claim kernel: ONE sort
  settles claims two queries make on the same slot, one scatter per table
  array, and the losers claim again on the updated table.
* ``extract_chunk_fused`` — the rebuild chunk scan (LIVE mask + compaction
  ranks in the kernel, the compaction itself one scatter).

The windows cover the whole probe bound, so the linear ops are exact with
no jnp fallback; only an insert whose claimed slot went to another query
of the batch takes a second claim pass (``insert_retry_share`` reports the share
of a batch that does).

``twochoice_*`` (also driving ``cuckoo``) and ``chain_*`` keep the
sorted-slab treatment: ONE argsort of the batch, ONE pallas_call over
scalar-prefetched slab blocks, and the gated jnp fallback for queries whose
window escapes the slab.  Their rebuild-epoch ops cover grown new tables
with a **two-level tile map**: a first-level jnp pass
(``_resident_blockmap`` — histogram + top_k, no extra sort) picks up to
``NRES_CAP`` resident new-table blocks per query tile, and the probe2
kernels reduce over them on a ``(tiles, nres)`` grid.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref, window
from repro.kernels.probe import (DIRTY_CAP, HZB, NRES_CAP, QT, SLAB, WQT,
                                 _tc_rowslab, chain_probe2_tiles,
                                 chain_probe_tiles, claim_windows,
                                 extract_rows, probe2_windows, probe_windows,
                                 tc_insert_tiles, tc_lookup_tiles,
                                 tc_probe2_tiles)
from repro.kernels.window import LANES

I32 = jnp.int32
EMPTY, LIVE, TOMB, MIGRATED = 0, 1, 2, 3


def _pad_to(x: jax.Array, n: int, fill=0):
    return jnp.pad(x, (0, n - x.shape[0]), constant_values=fill)


# ---------------------------------------------------------------------------
# linear: per-query probe windows (kernels/window.py)
# ---------------------------------------------------------------------------

def _queries(tables, h0: jax.Array, keys: jax.Array, max_probes: int):
    """Kernel operands of a query batch padded to a ``WQT`` multiple: the
    start lanes and keys as ``[Qpad, 1]`` columns and the (key, state)
    windows of ``tables`` (a key/state pair) as ``[Qpad, W]``."""
    q = keys.shape[0]
    qpad = -(-q // WQT) * WQT
    h0p = _pad_to(h0.astype(I32), qpad)
    kw, sw = window.windows(tables, h0p, tables[0].shape[0], max_probes)
    return ((h0p % LANES)[:, None], _pad_to(keys.astype(I32), qpad)[:, None],
            kw, sw)


def _lanes_to_slots(h0: jax.Array, lane: jax.Array) -> jax.Array:
    """Window lanes ``[Qpad, 1]`` -> unwrapped slots ``[Q]`` (``>= h0``),
    -1 where absent."""
    lane = lane[:h0.shape[0], 0]
    return jnp.where(lane >= 0, window.slot(h0, lane), -1)


def _kernel_probe(tkey, tstate, h0, keys, max_probes: int):
    """The lookup kernel over gathered windows: the unwrapped slot of each
    key's LIVE entry, -1 if absent."""
    hit = probe_windows(*_queries((tkey, tstate), h0, keys, max_probes),
                        max_probes=max_probes)
    return _lanes_to_slots(h0, hit)


def _kernel_claim(tkey, tstate, h0, keys, max_probes: int):
    """``window.insert``'s claim pass through the claim kernel."""
    q = keys.shape[0]
    hit, free = claim_windows(*_queries((tkey, tstate), h0, keys, max_probes),
                              max_probes=max_probes)
    return hit[:q, 0], free[:q, 0]


@partial(jax.jit, static_argnames=("max_probes", "with_loc"))
def probe_lookup(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                 h0: jax.Array, qkey: jax.Array, *, max_probes: int = 64,
                 with_loc: bool = False):
    """Batched linear-probe lookup. Returns (found[Q], val[Q]), or
    (found, val, loc[Q]) when ``with_loc`` — ``loc`` is the hit's unwrapped
    slot (``>= h0``, ``loc % C`` physical; -1 on miss), the probe telemetry
    input for the elastic policy's expensive-lookup counter.

    Args:
      tkey/tval/tstate: table arrays [C].
      h0: start slot per query (hash(key) % C), [Q].
      qkey: query keys [Q].
    """
    c = tkey.shape[0]
    loc = _kernel_probe(tkey, tstate, h0, qkey, max_probes)
    found = loc >= 0
    val = jnp.where(found, tval[loc % c], 0)
    if with_loc:
        return found, val, loc
    return found, val


@partial(jax.jit, static_argnames=("max_probes",))
def ordered_lookup(old_tables, new_tables, hazard_key, hazard_val, hazard_live,
                   h0_old, h0_new, qkey, *, max_probes: int = 64):
    """UNFUSED rebuild-epoch lookup: old table -> hazard buffer -> new table
    (the paper's Lemma 4.1 order), each table through its own lookup
    pallas_call.  Kept as the comparison baseline for
    ``ordered_lookup_fused`` (see bench_rebuild's fused=on|off axis)."""
    f_old, v_old = probe_lookup(*old_tables, h0_old, qkey,
                                max_probes=max_probes)
    eq = (qkey[:, None] == hazard_key[None, :]) & hazard_live[None, :]
    f_hz = eq.any(-1)
    v_hz = jnp.take(hazard_val, jnp.argmax(eq, axis=-1))
    f_new, v_new = probe_lookup(*new_tables, h0_new, qkey,
                                max_probes=max_probes)
    found = f_old | f_hz | f_new
    val = jnp.where(f_old, v_old, jnp.where(f_hz, v_hz, v_new))
    return found, val


def _probe2(old_tables, new_tables, hazard_key, hazard_live, h0_old, h0_new,
            keys, max_probes: int):
    """The ONE probe2 pallas_call of the fused rebuild-epoch ops.  Returns
    (old slot, hazard index, new slot) per query, -1 where absent; the slots
    are unwrapped as in ``probe_lookup``."""
    off_o, qk, *old_w = _queries((old_tables[0], old_tables[2]), h0_old,
                                 keys, max_probes)
    off_n, _, *new_w = _queries((new_tables[0], new_tables[2]), h0_new, keys,
                                max_probes)
    ch = hazard_key.shape[0]
    chp = ch if ch <= HZB else -(-ch // HZB) * HZB
    hit_o, hz, hit_n = probe2_windows(
        off_o, off_n, qk, old_w, new_w, _pad_to(hazard_key, chp)[None],
        _pad_to(hazard_live.astype(I32), chp)[None], max_probes=max_probes)
    return (_lanes_to_slots(h0_old, hit_o), hz[:keys.shape[0], 0],
            _lanes_to_slots(h0_new, hit_n))


@partial(jax.jit, static_argnames=("max_probes",))
def ordered_lookup_fused(old_tables, new_tables, hazard_key, hazard_val,
                         hazard_live, h0_old, h0_new, qkey, *,
                         max_probes: int = 64):
    """FUSED rebuild-epoch lookup: ONE pallas_call resolves the old table,
    the hazard buffer and the new table; the Lemma-4.1 priority (old >
    hazard > new) picks the answer.  Returns (found[Q], val[Q])."""
    loc_o, hz, loc_n = _probe2(old_tables, new_tables, hazard_key,
                               hazard_live, h0_old, h0_new, qkey, max_probes)
    f_old, f_hz, f_new = loc_o >= 0, hz >= 0, loc_n >= 0
    v_old = old_tables[1][loc_o % old_tables[1].shape[0]]
    v_hz = hazard_val[jnp.maximum(hz, 0)]
    v_new = new_tables[1][loc_n % new_tables[1].shape[0]]
    found = f_old | f_hz | f_new
    val = jnp.where(f_old, v_old,
                    jnp.where(f_hz, v_hz, jnp.where(f_new, v_new, 0)))
    return found, val


@partial(jax.jit, static_argnames=("max_probes",))
def probe_insert(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                 h0: jax.Array, keys: jax.Array, vals: jax.Array,
                 mask: jax.Array, *, max_probes: int = 64):
    """Batched linear-probe INSERT (``window.insert``) with the claim
    kernel as its claim pass: one claim-settling sort and one scatter per
    table array per pass; a claim that lost its slot (``window.settle``)
    claims again on the updated table (a second pass, rare at deployment
    load — ``insert_retry_share``).

    Caller contract: ``mask`` is winner-filtered (at most one True per
    distinct key; use ``buckets.batch_winners``).  Set semantics: ok=False
    if the key is already LIVE or no free slot exists within
    ``max_probes``.

    Returns (tkey', tval', tstate', ok[Q]).
    """
    return window.insert(tkey, tval, tstate, h0, keys, vals, mask,
                         max_probes, claim_pass=_kernel_claim)


@partial(jax.jit, static_argnames=("max_probes",))
def probe_insert_either(a, b, into_b: jax.Array, h0: jax.Array,
                        keys: jax.Array, vals: jax.Array, mask: jax.Array, *,
                        max_probes: tuple[int, int] = (64, 64)):
    """``probe_insert`` into the (key, val, state) triple ``b`` where
    ``into_b`` holds, else into ``a`` (``window.insert_either``: one claim
    loop carries both tables, the claim kernel reads the target).  The
    same caller contract.  Returns (a', b', ok[Q])."""
    return window.insert_either(a, b, into_b, h0, keys, vals, mask,
                                max_probes, claim_pass=_kernel_claim)


@partial(jax.jit, static_argnames=("max_probes",))
def insert_retry_share(tkey: jax.Array, tstate: jax.Array, h0: jax.Array,
                       keys: jax.Array, mask: jax.Array, *,
                       max_probes: int = 64):
    """Share of a masked insert batch that ``probe_insert`` sends through a
    second claim pass (its first claim lost the slot) — the same claim
    kernel and settling sort."""
    return window.retry_share(tkey, tstate, h0, keys, mask, max_probes,
                              claim_pass=_kernel_claim)


@partial(jax.jit, static_argnames=("max_probes",))
def probe_delete(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                 h0: jax.Array, keys: jax.Array, mask: jax.Array, *,
                 max_probes: int = 64):
    """Batched linear-probe DELETE: the lookup kernel's hit slot + ONE
    tombstone scatter (no second probe pass).

    Caller contract: ``mask`` is winner-filtered (at most one True per
    distinct key; use ``buckets.batch_winners``), so distinct masked keys
    occupy distinct slots and the scatter cannot conflict.

    Returns (tstate', ok[Q]).
    """
    del tval   # the table triple is the uniform operand of the linear ops
    c = tkey.shape[0]
    loc = _kernel_probe(tkey, tstate, h0, keys, max_probes)
    ok = mask & (loc >= 0)
    return tstate.at[jnp.where(ok, loc % c, c)].set(TOMB, mode="drop"), ok


@partial(jax.jit, static_argnames=("max_probes",))
def ordered_delete_fused(old_tables, new_tables, hazard_key, hazard_val,
                         hazard_live, h0_old, h0_new, keys, mask, *,
                         max_probes: int = 64):
    """FUSED rebuild-epoch delete (paper Alg. 5): the SAME probe2 pass as
    the ordered lookup, then three scatters land the result — tombstone the
    old-table slot, or clear the hazard live bit (LOGICALLY_REMOVED on an
    in-flight entry; landing drops it), or tombstone the new-table slot.

    Caller contract: ``mask`` is winner-filtered.  Returns
    (old_state', new_state', hazard_live', ok[Q]).
    """
    del hazard_val
    c_old, c_new = old_tables[0].shape[0], new_tables[0].shape[0]
    ch = hazard_key.shape[0]
    loc_o, hz, loc_n = _probe2(old_tables, new_tables, hazard_key,
                               hazard_live, h0_old, h0_new, keys, max_probes)
    ok_old = mask & (loc_o >= 0)
    ok_hz = mask & ~ok_old & (hz >= 0)
    ok_new = mask & ~ok_old & ~ok_hz & (loc_n >= 0)
    old_state = old_tables[2].at[
        jnp.where(ok_old, loc_o % c_old, c_old)].set(TOMB, mode="drop")
    new_state = new_tables[2].at[
        jnp.where(ok_new, loc_n % c_new, c_new)].set(TOMB, mode="drop")
    kill = jnp.zeros_like(hazard_live).at[
        jnp.where(ok_hz, hz, ch)].set(True, mode="drop")
    return old_state, new_state, hazard_live & ~kill, ok_old | ok_hz | ok_new


@partial(jax.jit, static_argnames=("chunk",))
def extract_chunk_fused(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                        cursor: jax.Array, *, chunk: int):
    """Rebuild chunk scan via the extract kernel: ONE pallas_call finds the
    LIVE slots of ``[cursor, cursor + chunk)`` and their compaction ranks;
    one scatter per hazard array compacts them, one scatter marks them
    MIGRATED.

    Returns (tstate', hkeys[chunk], hvals[chunk], hlive[chunk] bool,
    new_cursor) — identical set contents to the jnp scan, with the hazard
    entries compacted to the front.
    """
    c = tkey.shape[0]
    rows = -(-chunk // LANES)
    pos = cursor + jnp.arange(rows * LANES, dtype=I32)
    src = jnp.minimum(pos, c - 1)
    live, rank = extract_rows(tstate[src].reshape(rows, LANES),
                              jnp.clip(c - cursor, 0, chunk))
    live = live.reshape(-1)[:chunk] != 0
    dest = jnp.where(live, rank.reshape(-1)[:chunk], chunk)
    src = src[:chunk]
    hk = jnp.zeros((chunk,), I32).at[dest].set(tkey[src], mode="drop")
    hv = jnp.zeros((chunk,), I32).at[dest].set(tval[src], mode="drop")
    hl = jnp.arange(chunk, dtype=I32) < live.sum()
    tstate2 = tstate.at[jnp.where(live, pos[:chunk], c)].set(
        MIGRATED, mode="drop")
    new_cursor = jnp.minimum(cursor + chunk, c).astype(I32)
    return tstate2, hk, hv, hl, new_cursor


# ---------------------------------------------------------------------------
# sorted-slab helpers (twochoice / cuckoo / chain)
# ---------------------------------------------------------------------------

def _pad_table(arrays, c: int, max_probes: int):
    """Pad table arrays with a wrapped copy (probes never wrap in-kernel),
    then to a SLAB multiple plus one spare block (block s+1 always valid);
    padding slots are EMPTY so probes terminate there."""
    cpad = -(-(c + max_probes) // SLAB) * SLAB + SLAB
    return tuple(_pad_to(jnp.concatenate([a, a[:max_probes]]), cpad)
                 for a in arrays)


def _sort_pad_queries(order, qpad, *arrays):
    """Apply the shared sort and pad to a QT multiple by REPLICATING the last
    sorted element (edge padding).  Padding with a constant sentinel would
    break the slab math: an h0=0 pad in a tile whose slab base is > 0 reads
    complete=False and drags min-based tile bases to block 0, firing the
    oracle fallback on every non-QT-multiple batch.  Edge pads stay inside
    their tile's slab, and their results land in the discarded tail of the
    unsort (positions >= q)."""
    return tuple(jnp.pad(a[order], (0, qpad - a.shape[0]), mode="edge")
                 for a in arrays)


def _tile_base(h0_sorted: jax.Array, tiles: int, cpad: int) -> jax.Array:
    """Per-tile slab block index of a SORTED start-slot array (the tile's
    first element is its min), clipped so block s+1 stays in range."""
    base = h0_sorted.reshape(tiles, QT)[:, 0] // SLAB
    return jnp.minimum(base.astype(I32), cpad // SLAB - 2)


def _resident_blockmap(blk_sorted: jax.Array, tiles: int, nblocks: int,
                       nres: int) -> jax.Array:
    """First level of the two-level tile map: per tile, the ``nres``
    most-populated target blocks of the tile's queries (a vectorized
    histogram + ``top_k`` — no sort primitive, so the 1-sort/1-pallas_call
    budget is untouched).  ``blk_sorted`` is each query's target block index
    in the sorted batch order.  A query whose block is not among its tile's
    residents keeps ``complete=False`` in the kernel and is recovered by the
    gated jnp fallback.  Entries are clipped to ``nblocks - 2`` so the
    resident pair ``(b, b+1)`` stays in range; a window anchored at the
    query's own block always covers it (``max_probes <= SLAB``).
    Returns [nres, tiles]."""
    blk = blk_sorted.reshape(tiles, QT)
    hist = jnp.zeros((tiles, nblocks), I32).at[
        jnp.arange(tiles, dtype=I32)[:, None], blk].add(1)
    _, top = jax.lax.top_k(hist, nres)
    return jnp.minimum(top.astype(I32), nblocks - 2).T


# ---------------------------------------------------------------------------
# twochoice: both row choices expand into one sorted entry batch
# ---------------------------------------------------------------------------

def _tc_pad_rows(arrays, b: int, slab_r: int):
    """Row-pad [B, W] tables to a SLAB_R multiple plus one spare block
    (pad rows are EMPTY, so they can never satisfy a lookup or a claim)."""
    bpad = -(-b // slab_r) * slab_r + slab_r
    return tuple(jnp.pad(a, ((0, bpad - b), (0, 0))) for a in arrays)


def _tc_expand_sort(rows_a, rows_b, bpad: int, slab_r: int, *arrays):
    """Expand per-query arrays into the [2Q] entry batch (a-rows first, then
    b-rows), apply the ONE shared row-index sort + edge pad, and derive the
    per-tile row-block map.  Returns (order, epad, rows_sorted,
    sorted_arrays, slab_base) — the lookup and insert paths share this so
    their slab math can never diverge."""
    rows = jnp.concatenate([rows_a, rows_b])
    dup = [jnp.concatenate([a, a]) for a in arrays]
    e = rows.shape[0]
    order = jnp.argsort(rows)
    epad = -(-e // QT) * QT
    rs, *sorted_arrays = _sort_pad_queries(order, epad, rows, *dup)
    tiles = epad // QT
    base = rs.reshape(tiles, QT)[:, 0] // slab_r
    slab_base = jnp.minimum(base.astype(I32), bpad // slab_r - 2)
    return order, epad, rs, sorted_arrays, slab_base


@jax.jit
def twochoice_lookup(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                     rows_a: jax.Array, rows_b: jax.Array, qkey: jax.Array):
    """Fused twochoice lookup: the 2Q entry expansion (each query's two row
    choices), ONE argsort keyed on the row index, ONE pallas_call of the
    W-wide row-gather kernel, then a per-query recombine (a-row priority —
    the same tie-break as ``buckets.twochoice_lookup``).

    Returns (found[Q], val[Q], loc[Q] flat slot index or -1) — ``loc`` is
    reused by ``twochoice_delete`` so deleting never probes twice.
    """
    b, w = tkey.shape
    q = qkey.shape[0]
    e = 2 * q
    slab_r = _tc_rowslab(w)
    tk, tv, ts = _tc_pad_rows((tkey, tval, tstate), b, slab_r)
    order, epad, rs, (qks,), slab_base = _tc_expand_sort(
        rows_a, rows_b, tk.shape[0], slab_r, qkey)

    found_s, val_s, loc_s, complete_s = tc_lookup_tiles(
        tk, tv, ts, rs, qks, slab_base)

    need = ~complete_s

    def fallback(fvl):
        f0, v0, l0 = fvl
        fb_f, fb_v, fb_l = ref.tc_row_lookup_ref(tkey, tval, tstate, rs, qks)
        return (jnp.where(need, fb_f, f0), jnp.where(need, fb_v, v0),
                jnp.where(need, fb_l, l0))

    found_s, val_s, loc_s = jax.lax.cond(need.any(), fallback, lambda x: x,
                                         (found_s, val_s, loc_s))

    fe = jnp.zeros((e,), jnp.bool_).at[order].set(found_s[:e])
    ve = jnp.zeros((e,), I32).at[order].set(val_s[:e])
    le = jnp.full((e,), -1, I32).at[order].set(loc_s[:e])
    f_a, f_b = fe[:q], fe[q:]
    found = f_a | f_b
    val = jnp.where(f_a, ve[:q], ve[q:])
    loc = jnp.where(f_a, le[:q], jnp.where(f_b, le[q:], -1))
    return found, val, loc


@partial(jax.jit, static_argnames=("max_rounds"))
def twochoice_insert(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                     rows_a: jax.Array, rows_b: jax.Array, keys: jax.Array,
                     vals: jax.Array, mask: jax.Array, *,
                     max_rounds: int = 8):
    """Batched twochoice INSERT via the claim kernel + one scatter.

    Caller contract: ``mask`` is winner-filtered.  Set semantics: ok=False
    if the key is LIVE in either row or both rows are full.  The kernel
    claims per row-entry; here the a-claim shadows the b-claim of the same
    query, cross-tile slot collisions keep the first claimant (batch order),
    and everything else — escaped windows, lost claims, locally-full rows —
    re-runs on the jnp oracle (gated).

    Returns (tkey', tval', tstate', ok[Q]).
    """
    b, w = tkey.shape
    q = keys.shape[0]
    e = 2 * q
    nslots = b * w
    slab_r = _tc_rowslab(w)
    tk, ts = _tc_pad_rows((tkey, tstate), b, slab_r)
    order, epad, rs, (qks,), slab_base = _tc_expand_sort(
        rows_a, rows_b, tk.shape[0], slab_r, keys)
    qms = _pad_to(jnp.concatenate([mask, mask])[order], epad, fill=False)

    present_s, claim_s, complete_s = tc_insert_tiles(
        tk, ts, rs, qks, qms.astype(I32), slab_base)

    pe = jnp.zeros((e,), jnp.bool_).at[order].set(present_s[:e])
    ce = jnp.full((e,), -1, I32).at[order].set(claim_s[:e])
    cpl = jnp.zeros((e,), jnp.bool_).at[order].set(complete_s[:e])
    present = pe[:q] | pe[q:]
    compl2 = cpl[:q] & cpl[q:]     # presence known for BOTH rows
    c_a, c_b = ce[:q], ce[q:]
    cand = jnp.where(compl2 & ~present,
                     jnp.where(c_a >= 0, c_a, c_b), -1)

    claimed = cand >= 0
    phys = jnp.where(claimed, cand, nslots)
    idx = jnp.arange(q, dtype=I32)
    first = jnp.full((nslots,), q, I32).at[phys].min(idx, mode="drop")
    keep = claimed & (first[jnp.clip(phys, 0, nslots - 1)] == idx)

    wp = jnp.where(keep, phys, nslots)
    tkey2 = tkey.reshape(-1).at[wp].set(keys, mode="drop").reshape(b, w)
    tval2 = tval.reshape(-1).at[wp].set(vals, mode="drop").reshape(b, w)
    tstate2 = tstate.reshape(-1).at[wp].set(LIVE, mode="drop").reshape(b, w)
    ok = keep

    need = mask & ~keep & ~present

    def fallback(op):
        k, v, s, ok0 = op
        fb_k, fb_v, fb_s, fb_ok = ref.tc_insert_ref(
            k, v, s, rows_a, rows_b, keys, vals, need, max_rounds)
        return fb_k, fb_v, fb_s, ok0 | fb_ok

    tkey2, tval2, tstate2, ok = jax.lax.cond(
        need.any(), fallback, lambda op: op, (tkey2, tval2, tstate2, ok))
    return tkey2, tval2, tstate2, ok


@jax.jit
def twochoice_delete(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                     rows_a: jax.Array, rows_b: jax.Array, keys: jax.Array,
                     mask: jax.Array):
    """Batched twochoice DELETE: reuses the fused lookup's location output —
    one kernel pass, one tombstone scatter, never a second probe (the jnp
    ``twochoice_delete`` re-gathers both rows to find the slot again).

    Caller contract: ``mask`` is winner-filtered.  Returns (tstate', ok[Q]).
    """
    b, w = tkey.shape
    found, _val, loc = twochoice_lookup(tkey, tval, tstate, rows_a, rows_b,
                                        keys)
    ok = mask & found
    tstate2 = tstate.reshape(-1).at[jnp.where(ok, loc, b * w)].set(
        TOMB, mode="drop").reshape(b, w)
    return tstate2, ok


# ---------------------------------------------------------------------------
# twochoice rebuild-epoch ops: ONE sort + ONE probe2-style pallas_call
# ---------------------------------------------------------------------------

def _tc_probe2_run(old_t, new_t, hazard_key, hazard_val, hazard_live,
                   rows_a_old, rows_b_old, rows_a_new, rows_b_new, keys,
                   nres_cap: int = NRES_CAP):
    """Shared prep + launch for the fused twochoice rebuild-epoch ops: the
    2Q entry expansion (each query's two row choices, paired old/new), ONE
    argsort keyed on the OLD row, the two-level resident map for the new
    table's row-blocks, and ONE ``tc_probe2`` pallas_call.  Returns the
    per-entry kernel outputs unsorted back to entry order."""
    b_old, w = old_t[0].shape
    b_new = new_t[0].shape[0]
    slab_r = _tc_rowslab(w)
    old_p = _tc_pad_rows(old_t, b_old, slab_r)
    new_p = _tc_pad_rows(new_t, b_new, slab_r)

    orow = jnp.concatenate([rows_a_old, rows_b_old])
    nrow = jnp.concatenate([rows_a_new, rows_b_new])
    qk2 = jnp.concatenate([keys, keys])
    e = orow.shape[0]
    order = jnp.argsort(orow)
    epad = -(-e // QT) * QT
    ors, nrs, qks = _sort_pad_queries(order, epad, orow, nrow, qk2)
    tiles = epad // QT
    obase = jnp.minimum(
        (ors.reshape(tiles, QT)[:, 0] // slab_r).astype(I32),
        old_p[0].shape[0] // slab_r - 2)
    nblocks_new = new_p[0].shape[0] // slab_r
    nres = min(nres_cap, nblocks_new - 1)
    slab2 = jnp.concatenate([
        obase[None], _resident_blockmap(nrs // slab_r, tiles, nblocks_new,
                                        nres)])

    outs = tc_probe2_tiles(old_p, new_p, hazard_key, hazard_val,
                           hazard_live.astype(I32), ors, nrs, qks, slab2)
    unsorted = tuple(jnp.zeros((e,), o.dtype).at[order].set(o[:e])
                     for o in outs)
    return unsorted


def _tc_ordered_combine(outs, hazard_key, hazard_val, q: int):
    """Recombine the per-entry probe2 components into per-query ordered
    results (a-row priority within each table, old > hazard > new across
    them).  Returns (f_old, v_old, l_old, f_hz, hz_idx, v_hz, f_new, v_new,
    l_new, complete)."""
    f_o, v_o, l_o, c_o, hz, f_n, v_n, l_n, c_n = outs
    fo = f_o[:q] | f_o[q:]
    vo = jnp.where(f_o[:q], v_o[:q], v_o[q:])
    lo = jnp.where(f_o[:q], l_o[:q], l_o[q:])
    co = c_o[:q] & c_o[q:]              # absence needs BOTH rows covered
    hzq = hz[:q]                        # both entries carry the same key
    f_hz = hzq >= 0
    v_hz = jnp.take(hazard_val, jnp.clip(hzq, 0, hazard_key.shape[0] - 1))
    fn = f_n[:q] | f_n[q:]
    vn = jnp.where(f_n[:q], v_n[:q], v_n[q:])
    ln = jnp.where(f_n[:q], l_n[:q], l_n[q:])
    cn = c_n[:q] & c_n[q:]
    complete = co & (fo | f_hz | cn)
    return fo, vo, lo, f_hz, hzq, v_hz, fn, vn, ln, complete


@partial(jax.jit, static_argnames=("nres_cap"))
def twochoice_ordered_lookup(old_t, new_t, hazard_key, hazard_val,
                             hazard_live, rows_a_old, rows_b_old,
                             rows_a_new, rows_b_new, qkey, *,
                             nres_cap: int = NRES_CAP):
    """FUSED twochoice rebuild-epoch lookup: ONE argsort (the 2Q entry batch
    keyed on the old table's row index) + ONE pallas_call emit the
    Lemma-4.1-ordered result — previously this composed TWO fused
    single-table passes around a separate hazard compare.  Queries the
    kernel could not determine (either row's window escaped) fall back to
    the jnp oracle (gated — free when nothing escapes).

    Returns (found[Q], val[Q])."""
    q = qkey.shape[0]
    outs = _tc_probe2_run(old_t, new_t, hazard_key, hazard_val, hazard_live,
                          rows_a_old, rows_b_old, rows_a_new, rows_b_new,
                          qkey, nres_cap)
    (fo, vo, _lo, f_hz, _hzq, v_hz, fn, vn, _ln,
     complete) = _tc_ordered_combine(outs, hazard_key, hazard_val, q)
    found = (fo | f_hz | fn) & complete
    val = jnp.where(
        complete,
        jnp.where(fo, vo, jnp.where(f_hz, v_hz, jnp.where(fn, vn, 0))), 0)

    need = ~complete

    def fallback(fv):
        f0, v0 = fv
        fa, va, _ = ref.tc_row_lookup_ref(*old_t, rows_a_old, qkey)
        fb, vb, _ = ref.tc_row_lookup_ref(*old_t, rows_b_old, qkey)
        f_oldr, v_oldr = fa | fb, jnp.where(fa, va, vb)
        eq = (qkey[:, None] == hazard_key[None, :]) & hazard_live[None, :]
        fh = eq.any(-1)
        vh = jnp.take(hazard_val, jnp.argmax(eq, axis=-1))
        fna, vna, _ = ref.tc_row_lookup_ref(*new_t, rows_a_new, qkey)
        fnb, vnb, _ = ref.tc_row_lookup_ref(*new_t, rows_b_new, qkey)
        f_newr, v_newr = fna | fnb, jnp.where(fna, vna, vnb)
        fb_f = f_oldr | fh | f_newr
        fb_v = jnp.where(f_oldr, v_oldr,
                         jnp.where(fh, vh, jnp.where(f_newr, v_newr, 0)))
        return jnp.where(need, fb_f, f0), jnp.where(need, fb_v, v0)

    return jax.lax.cond(need.any(), fallback, lambda fv: fv, (found, val))


@partial(jax.jit, static_argnames=("nres_cap"))
def twochoice_ordered_delete(old_t, new_t, hazard_key, hazard_val,
                             hazard_live, rows_a_old, rows_b_old,
                             rows_a_new, rows_b_new, keys, mask, *,
                             nres_cap: int = NRES_CAP):
    """FUSED twochoice rebuild-epoch delete (paper Alg. 5): the SAME single
    probe2-style pass as the ordered lookup resolves old-slot / hazard-index
    / new-slot, then three scatters land the tombstones and the hazard kill.

    Caller contract: ``mask`` is winner-filtered.  Returns
    (old_state', new_state', hazard_live', ok[Q])."""
    b_old, w = old_t[0].shape
    b_new = new_t[0].shape[0]
    ch = hazard_key.shape[0]
    q = keys.shape[0]
    outs = _tc_probe2_run(old_t, new_t, hazard_key, hazard_val, hazard_live,
                          rows_a_old, rows_b_old, rows_a_new, rows_b_new,
                          keys, nres_cap)
    (fo, _vo, lo, f_hz, hzq, _vhz, fn, _vn, ln,
     complete) = _tc_ordered_combine(outs, hazard_key, hazard_val, q)

    # ordered landing: old hit > hazard hit > new hit.  An old hit is
    # trusted even when ``complete`` is False (priority already determined);
    # such queries are excluded from the fallback so they cannot double-
    # delete a second instance downstream.
    ok_old = mask & fo
    ok_hz = mask & complete & ~fo & f_hz
    ok_new = mask & complete & ~fo & ~f_hz & fn

    old_state = old_t[2].reshape(-1).at[
        jnp.where(ok_old, lo, b_old * w)].set(TOMB, mode="drop").reshape(
        b_old, w)
    new_state = new_t[2].reshape(-1).at[
        jnp.where(ok_new, ln, b_new * w)].set(TOMB, mode="drop").reshape(
        b_new, w)
    kill = jnp.zeros_like(hazard_live).at[
        jnp.where(ok_hz, hzq, ch)].set(True, mode="drop")
    hz_live = hazard_live & ~kill
    ok = ok_old | ok_hz | ok_new

    need = mask & ~fo & ~complete

    def fallback(op):
        os_, ns_, hl_, ok0 = op
        fb_os, ok_o = ref.tc_delete_ref(old_t[0], old_t[1], os_,
                                        rows_a_old, rows_b_old, keys, need)
        pend = need & ~ok_o
        eq = (keys[:, None] == hazard_key[None, :]) & hl_[None, :]
        hz_hit = eq.any(-1) & pend
        kill2 = jnp.zeros_like(hl_).at[
            jnp.where(hz_hit, jnp.argmax(eq, axis=-1), ch)].set(
            True, mode="drop")
        fb_ns, ok_n = ref.tc_delete_ref(new_t[0], new_t[1], ns_,
                                        rows_a_new, rows_b_new, keys,
                                        pend & ~hz_hit)
        return fb_os, fb_ns, hl_ & ~kill2, ok0 | ok_o | hz_hit | ok_n

    old_state, new_state, hz_live, ok = jax.lax.cond(
        need.any(), fallback, lambda op: op,
        (old_state, new_state, hz_live, ok))
    return old_state, new_state, hz_live, ok


# ---------------------------------------------------------------------------
# chain: segment-window ops over the arena-sorted node layout
# ---------------------------------------------------------------------------
#
# The chain arena is kept bucket-sorted and tombstone-compacted by
# ``chain_compact_fused``: bucket b's nodes occupy [bstart[b],
# bstart[b]+blen[b]), so a chain probe is the same slab-window reduction as
# a linear probe with h0 = bstart[b] and the segment length as the
# termination bound.  Nodes inserted since the last compaction form a
# contiguous DIRTY tail resolved by a dense window compare (static
# ``DIRTY_CAP`` window — the hazard-buffer treatment); a tail grown past the
# window escapes to the pointer-chasing jnp reference (``ref.chain_*_ref``)
# via the same gated-fallback pattern as every other fused op.  Argument
# convention: ``arena = (akey, aval, astate)``, ``links = (anext, heads)``
# (consumed only by the fallback), ``seg = (bstart, blen, sorted_upto,
# dirty)``.

def _chain_dirty_window(arena, sorted_upto, dirty, qkey,
                        dirty_cap: int = DIRTY_CAP):
    """Dense compare of the query batch against the arena's dirty tail.

    The window is the static-size slice [base, base + size) with
    ``base = min(sorted_upto, N - size)`` — clamping keeps the slice in
    bounds while still covering the whole tail whenever ``dirty`` fits.
    Positions below ``sorted_upto`` (clamp overlap with the sorted region)
    are excluded; the kernel owns those.  Returns (found, val, loc_abs,
    covered) with ``covered`` a scalar: False iff the tail outgrew the
    window and absence can no longer be proven here.
    """
    akey, aval, astate = arena
    n = akey.shape[0]
    size = min(dirty_cap, n)
    base = jnp.minimum(sorted_upto, n - size).astype(I32)
    wk = jax.lax.dynamic_slice(akey, (base,), (size,))
    wv = jax.lax.dynamic_slice(aval, (base,), (size,))
    ws = jax.lax.dynamic_slice(astate, (base,), (size,))
    pos = base + jnp.arange(size, dtype=I32)
    valid = (ws == LIVE) & (pos >= sorted_upto)
    eq = (qkey[:, None] == wk[None, :]) & valid[None, :]
    hit = eq.any(-1)
    i = jnp.argmax(eq, axis=-1).astype(I32)
    val = jnp.where(hit, jnp.take(wv, i), 0)
    loc = jnp.where(hit, base + i, -1)
    covered = sorted_upto + dirty <= base + size
    return hit, val, loc, covered


def _chain_run(arena, seg, bq, qkey, max_chain: int,
               dirty_cap: int = DIRTY_CAP):
    """Shared prep + launch for the single-arena chain ops: the ONE sort
    (stable argsort on the bucket — ``bstart`` is nondecreasing in the
    bucket, so segment starts sort with it, and the insert path reuses the
    same order for its head relink), the ONE chain-probe pallas_call, and
    the dirty-tail window merge.  Returns (order, sorted (keys, buckets),
    (found, val, loc_physical, need)) — all in sorted coordinates."""
    bstart, blen, sorted_upto, dirty = seg
    n = arena[0].shape[0]
    q = qkey.shape[0]
    h0 = bstart[bq]
    qlen = blen[bq]
    tk, tv, ts = _pad_table(arena, n, max_chain)

    order = jnp.argsort(bq)
    qpad = -(-q // QT) * QT
    h0s, qls, qks, bqs = _sort_pad_queries(order, qpad, h0, qlen, qkey, bq)
    tiles = qpad // QT
    slab_base = _tile_base(h0s, tiles, tk.shape[0])

    f_s, v_s, l_s, c_s = chain_probe_tiles(
        tk, tv, ts, h0s, qls, qks, slab_base, max_probes=max_chain)

    fw, vw, lw, covered = _chain_dirty_window(arena, sorted_upto, dirty, qks,
                                              dirty_cap)
    found_s = f_s | fw
    val_s = jnp.where(f_s, v_s, vw)
    loc_s = jnp.where(f_s, l_s % n, lw)   # physical node index (-1 = absent)
    # unresolved: not found anywhere AND absence not proven (segment window
    # escaped / segment longer than max_chain / dirty tail past the window)
    need_s = ~found_s & (~c_s | ~covered)
    return order, (qks, bqs), (found_s, val_s, loc_s, need_s)


@partial(jax.jit, static_argnames=("max_chain", "dirty_cap"))
def chain_lookup_fused(arena, links, seg, bq, qkey, *, max_chain: int = 64,
                       dirty_cap: int = DIRTY_CAP):
    """Fused chain lookup: ONE argsort + ONE chain-probe pallas_call over
    the bucket-sorted segments, a dense dirty-tail window, and the
    pointer-chasing jnp reference as the gated fallback for unresolved
    queries.  Returns (found[Q], val[Q], loc[Q] node index or -1) — ``loc``
    is reused by the fused delete so deleting never probes twice."""
    q = qkey.shape[0]
    order, (qks, bqs), (found_s, val_s, loc_s, need_s) = _chain_run(
        arena, seg, bq, qkey, max_chain, dirty_cap)

    def fallback(fvl):
        f0, v0, l0 = fvl
        fb_f, fb_v, fb_l = ref.chain_lookup_ref(*arena, *links, bqs, qks,
                                                max_chain)
        return (jnp.where(need_s, fb_f, f0), jnp.where(need_s, fb_v, v0),
                jnp.where(need_s, fb_l, l0))

    found_s, val_s, loc_s = jax.lax.cond(need_s.any(), fallback, lambda x: x,
                                         (found_s, val_s, loc_s))

    found = jnp.zeros((q,), jnp.bool_).at[order].set(found_s[:q])
    val = jnp.zeros((q,), I32).at[order].set(val_s[:q])
    loc = jnp.full((q,), -1, I32).at[order].set(loc_s[:q])
    return found, val, loc


@partial(jax.jit, static_argnames=("max_chain", "dirty_cap"))
def chain_delete_fused(arena, links, seg, bq, keys, mask, *,
                       max_chain: int = 64,
                       dirty_cap: int = DIRTY_CAP):
    """Fused chain delete: the location-emitting probe run + ONE tombstone
    scatter (logical deletion; compaction reclaims).  Caller contract:
    ``mask`` is winner-filtered.  Returns (astate', ok[Q])."""
    n = arena[0].shape[0]
    q = keys.shape[0]
    qpad = -(-q // QT) * QT
    order, (qks, bqs), (found_s, _val_s, loc_s, need_s) = _chain_run(
        arena, seg, bq, keys, max_chain, dirty_cap)
    qms = _pad_to(mask[order], qpad, fill=False)

    ok_s = qms & found_s
    astate2 = arena[2].at[jnp.where(ok_s, loc_s, n)].set(TOMB, mode="drop")

    need = qms & need_s

    def fallback(op):
        s, ok = op
        fb_s, fb_ok = ref.chain_delete_ref(arena[0], arena[1], s, *links,
                                           bqs, qks, need, max_chain)
        return fb_s, ok | fb_ok

    astate2, ok_s = jax.lax.cond(need.any(), fallback, lambda op: op,
                                 (astate2, ok_s))

    ok = jnp.zeros((q,), jnp.bool_).at[order].set(ok_s[:q])
    return astate2, ok


@partial(jax.jit, static_argnames=("max_chain", "dirty_cap"))
def chain_insert_fused(arena, links, seg, free_stack, free_top, bq, keys,
                       vals, mask, *, max_chain: int = 64, dirty_cap: int = DIRTY_CAP):
    """Fused chain insert: the presence probe (kernel + dirty window +
    gated pointer fallback) and the head relink share the SAME stable sort
    keyed on the bucket, so the whole op is ONE argsort + ONE pallas_call.
    New nodes are allocated from the free-stack tail (positions ascend, so
    they extend the dirty window) and linked at their buckets' heads in
    original-index order — the identical linearization, node placement, and
    pointer structure as ``buckets.chain_insert``.

    Caller contract: ``mask`` is winner-filtered.  Returns
    (akey', aval', astate', anext', heads', free_top', ok[Q]).
    """
    akey, aval, astate = arena
    anext, heads = links
    n = akey.shape[0]
    nb = heads.shape[0]
    q = keys.shape[0]
    order, (qks, bqs), (found_s, _v, _l, need_s) = _chain_run(
        arena, seg, bq, keys, max_chain, dirty_cap)

    def fb_present(p):
        fb_f, _, _ = ref.chain_lookup_ref(akey, aval, astate, anext, heads,
                                          bqs, qks, max_chain)
        return jnp.where(need_s, fb_f, p)

    present_s = jax.lax.cond(need_s.any(), fb_present, lambda p: p, found_s)
    present = jnp.zeros((q,), jnp.bool_).at[order].set(present_s[:q])

    # allocation: identical linearization to buckets.chain_insert (want-rank
    # in original order pops ascending arena positions)
    want = mask & ~present
    rank = jnp.cumsum(want.astype(I32)) - 1
    can = want & (rank < free_top)
    node = free_stack[jnp.where(can, free_top - 1 - rank, 0)]
    wnode = jnp.where(can, node, n)
    akey2 = akey.at[wnode].set(keys, mode="drop")
    aval2 = aval.at[wnode].set(vals, mode="drop")
    astate2 = astate.at[wnode].set(LIVE, mode="drop")

    # head relink in the SAME sorted order (bucket asc, original index asc):
    # each inserted node chains to the NEXT inserted node of its bucket
    # (suffix-min scan — no second sort), the last one to the old head, and
    # the FIRST inserted node of each bucket becomes the new head
    # (prefix-max scan).
    can_s = can[order]
    node_s = node[order]
    b_s = bqs[:q]
    pos = jnp.arange(q, dtype=I32)
    w = jnp.where(can_s, pos, q)
    m = jnp.flip(jax.lax.cummin(jnp.flip(
        jnp.concatenate([w[1:], jnp.full((1,), q, I32)]))))
    nxt_idx = jnp.minimum(m, q - 1)
    same_b = (m < q) & (b_s[nxt_idx] == b_s)
    nxt_node = jnp.where(same_b, node_s[nxt_idx], heads[b_s])
    anext2 = anext.at[jnp.where(can_s, node_s, n)].set(nxt_node, mode="drop")
    wp = jnp.where(can_s, pos, -1)
    pm = jax.lax.cummax(jnp.concatenate([jnp.full((1,), -1, I32), wp[:-1]]))
    prev_idx = jnp.maximum(pm, 0)
    is_first = can_s & ((pm < 0) | (b_s[prev_idx] != b_s))
    heads2 = heads.at[jnp.where(is_first, b_s, nb)].set(node_s, mode="drop")

    free_top2 = free_top - jnp.sum(can.astype(I32))
    return akey2, aval2, astate2, anext2, heads2, free_top2, can


@partial(jax.jit, static_argnames=("nbuckets",))
def chain_compact_fused(akey, aval, astate, bq_nodes, *, nbuckets: int):
    """The arena-sorted compaction pass: ONE segmented sort keyed on
    (bucket, arena index) with dead nodes pushed past every bucket, then the
    compaction gather (the sort's permutation IS the `_extract_kernel`-style
    rank compaction, applied globally), per-bucket (start, len) offsets via
    a histogram + exclusive cumsum, and a vectorized pointer rebuild so the
    jnp reference paths stay valid (node i chains to i + 1 within its
    bucket).  Tombstoned/migrated nodes are physically reclaimed — the
    batched analogue of the paper's deferred call_rcu free.

    Returns (akey', aval', astate', anext', heads', free_stack', free_top',
    bstart, blen, sorted_upto).
    """
    n = akey.shape[0]
    idx = jnp.arange(n, dtype=I32)
    live = astate == LIVE
    sortkey = jnp.where(live, bq_nodes, nbuckets)
    order = jnp.argsort(sortkey)          # stable: (bucket, arena index)
    ls = live[order]
    akey2 = jnp.where(ls, akey[order], 0)
    aval2 = jnp.where(ls, aval[order], 0)
    astate2 = jnp.where(ls, LIVE, EMPTY).astype(I32)
    lcount = jnp.sum(live.astype(I32))
    counts = jnp.zeros((nbuckets,), I32).at[
        jnp.where(live, bq_nodes, nbuckets)].add(1, mode="drop")
    bstart = jnp.concatenate(
        [jnp.zeros((1,), I32), jnp.cumsum(counts)[:-1].astype(I32)])
    sb = sortkey[order]
    chain_on = ls & jnp.concatenate([sb[1:] == sb[:-1],
                                     jnp.zeros((1,), bool)])
    anext2 = jnp.where(chain_on, idx + 1, -1)
    heads2 = jnp.where(counts > 0, bstart, -1)
    free_stack2 = n - 1 - idx
    free_top2 = n - lcount
    return (akey2, aval2, astate2, anext2, heads2, free_stack2, free_top2,
            bstart, counts, lcount)


def _chain_probe2_run(old_arena, old_seg, new_arena, new_seg, hazard_key,
                      hazard_val, hazard_live, bq_old, bq_new, keys,
                      max_chain: int,
                      nres_cap: int = NRES_CAP, dirty_cap: int = DIRTY_CAP):
    """Shared prep + launch for the fused chain rebuild-epoch ops: the ONE
    argsort (keyed on the old arena's segment starts), the two-level tile
    map for the new arena's blocks, ONE chain_probe2 pallas_call, and the
    dirty-tail window merges for BOTH arenas.  Returns (order, sorted
    (keys, old buckets, new buckets), per-query Lemma-4.1 components)."""
    n_old = old_arena[0].shape[0]
    n_new = new_arena[0].shape[0]
    q = keys.shape[0]
    old_p = _pad_table(old_arena, n_old, max_chain)
    new_p = _pad_table(new_arena, n_new, max_chain)
    h0o = old_seg[0][bq_old]
    qlo = old_seg[1][bq_old]
    h0n = new_seg[0][bq_new]
    qln = new_seg[1][bq_new]

    order = jnp.argsort(h0o)
    qpad = -(-q // QT) * QT
    h0os, qlos, h0ns, qlns, qks, bqos, bqns = _sort_pad_queries(
        order, qpad, h0o, qlo, h0n, qln, keys, bq_old, bq_new)
    tiles = qpad // QT
    nblocks_new = new_p[0].shape[0] // SLAB
    nres = min(nres_cap, nblocks_new - 1)
    slab2 = jnp.concatenate([
        _tile_base(h0os, tiles, old_p[0].shape[0])[None],
        _resident_blockmap(h0ns // SLAB, tiles, nblocks_new, nres)])

    (f_o, v_o, l_o, c_o, hz, f_n, v_n, l_n, c_n) = chain_probe2_tiles(
        old_p, new_p, hazard_key, hazard_val, hazard_live.astype(I32),
        h0os, qlos, h0ns, qlns, qks, slab2, max_probes=max_chain)

    fwo, vwo, lwo, cov_o = _chain_dirty_window(old_arena, old_seg[2],
                                               old_seg[3], qks, dirty_cap)
    fwn, vwn, lwn, cov_n = _chain_dirty_window(new_arena, new_seg[2],
                                               new_seg[3], qks, dirty_cap)
    fo = f_o | fwo
    vo = jnp.where(f_o, v_o, vwo)
    lo = jnp.where(f_o, l_o % n_old, lwo)
    f_hz = hz >= 0
    v_hz = jnp.take(hazard_val, jnp.clip(hz, 0, hazard_key.shape[0] - 1))
    fn = f_n | fwn
    vn = jnp.where(f_n, v_n, vwn)
    ln = jnp.where(f_n, l_n % n_new, lwn)
    co = c_o & cov_o
    cn = c_n & cov_n
    # ordered-check refinement: an old hit settles the query outright (any
    # hit is real — windows and kernel both only report LIVE matches); absent
    # from old is only trusted with full old coverage, after which the dense
    # hazard compare and the new side (hit, or proven absent) settle it.
    complete = fo | (co & (f_hz | fn | cn))
    return order, (qks, bqos, bqns), (fo, vo, lo, f_hz, hz, v_hz, fn, vn,
                                      ln, complete)


@partial(jax.jit, static_argnames=("max_chain", "nres_cap",
                                   "dirty_cap"))
def chain_ordered_lookup(old_arena, old_links, old_seg, new_arena, new_links,
                         new_seg, hazard_key, hazard_val, hazard_live,
                         bq_old, bq_new, qkey, *, max_chain: int = 64,
                         nres_cap: int = NRES_CAP,
                         dirty_cap: int = DIRTY_CAP):
    """FUSED chain rebuild-epoch lookup: ONE argsort + ONE chain_probe2
    pallas_call emit the Lemma-4.1-ordered result (old arena -> hazard
    buffer -> new arena), with the two-level tile map keeping a grown new
    arena resident and both arenas' dirty tails merged by dense windows.
    Unresolved queries fall back to the pointer-chasing jnp ordered check
    (gated — free when nothing escapes).  Returns (found[Q], val[Q])."""
    q = qkey.shape[0]
    order, (qks, bqos, bqns), comps = _chain_probe2_run(
        old_arena, old_seg, new_arena, new_seg, hazard_key, hazard_val,
        hazard_live, bq_old, bq_new, qkey, max_chain,
        nres_cap, dirty_cap)
    (fo, vo, _lo, f_hz, _hz, v_hz, fn, vn, _ln, complete) = comps
    found_s = (fo | f_hz | fn) & complete
    val_s = jnp.where(
        complete,
        jnp.where(fo, vo, jnp.where(f_hz, v_hz, jnp.where(fn, vn, 0))), 0)

    need = ~complete

    def fallback(fv):
        f0, v0 = fv
        fb_f, fb_v = ref.chain_ordered_lookup_ref(
            old_arena, old_links, new_arena, new_links, hazard_key,
            hazard_val, hazard_live, bqos, bqns, qks, max_chain)
        return jnp.where(need, fb_f, f0), jnp.where(need, fb_v, v0)

    found_s, val_s = jax.lax.cond(need.any(), fallback, lambda fv: fv,
                                  (found_s, val_s))

    found = jnp.zeros((q,), jnp.bool_).at[order].set(found_s[:q])
    val = jnp.zeros((q,), I32).at[order].set(val_s[:q])
    return found, val


@partial(jax.jit, static_argnames=("max_chain", "nres_cap",
                                   "dirty_cap"))
def chain_ordered_delete(old_arena, old_links, old_seg, new_arena, new_links,
                         new_seg, hazard_key, hazard_val, hazard_live,
                         bq_old, bq_new, keys, mask, *, max_chain: int = 64,
                         nres_cap: int = NRES_CAP,
                         dirty_cap: int = DIRTY_CAP):
    """FUSED chain rebuild-epoch delete (paper Alg. 5): the SAME single
    chain_probe2 pass resolves old-node / hazard-index / new-node, then
    three scatters land the tombstones and the hazard kill.

    Caller contract: ``mask`` is winner-filtered.  Returns
    (old_astate', new_astate', hazard_live', ok[Q])."""
    n_old = old_arena[0].shape[0]
    n_new = new_arena[0].shape[0]
    ch = hazard_key.shape[0]
    q = keys.shape[0]
    qpad = -(-q // QT) * QT
    order, (qks, bqos, bqns), comps = _chain_probe2_run(
        old_arena, old_seg, new_arena, new_seg, hazard_key, hazard_val,
        hazard_live, bq_old, bq_new, keys, max_chain,
        nres_cap, dirty_cap)
    (fo, _vo, lo, f_hz, hz, _vhz, fn, _vn, ln, complete) = comps
    qms = _pad_to(mask[order], qpad, fill=False)

    # ordered landing: old hit > hazard hit > new hit.  An old hit is
    # trusted even when ``complete`` is False (priority already determined);
    # such queries are excluded from the fallback so they cannot double-
    # delete a second instance downstream.
    ok_old = qms & fo
    ok_hz = qms & complete & ~fo & f_hz
    ok_new = qms & complete & ~fo & ~f_hz & fn

    old_state = old_arena[2].at[
        jnp.where(ok_old, lo, n_old)].set(TOMB, mode="drop")
    new_state = new_arena[2].at[
        jnp.where(ok_new, ln, n_new)].set(TOMB, mode="drop")
    kill = jnp.zeros_like(hazard_live).at[
        jnp.where(ok_hz, hz, ch)].set(True, mode="drop")
    hz_live = hazard_live & ~kill
    ok_s = ok_old | ok_hz | ok_new

    need = qms & ~fo & ~complete

    def fallback(op):
        os_, ns_, hl_, ok = op
        fb_os, ok_o = ref.chain_delete_ref(old_arena[0], old_arena[1], os_,
                                           *old_links, bqos, qks, need,
                                           max_chain)
        pend = need & ~ok_o
        eq = (qks[:, None] == hazard_key[None, :]) & hl_[None, :]
        hz_hit = eq.any(-1) & pend
        kill2 = jnp.zeros_like(hl_).at[
            jnp.where(hz_hit, jnp.argmax(eq, axis=-1), ch)].set(
            True, mode="drop")
        fb_ns, ok_n = ref.chain_delete_ref(new_arena[0], new_arena[1], ns_,
                                           *new_links, bqns, qks,
                                           pend & ~hz_hit, max_chain)
        return fb_os, fb_ns, hl_ & ~kill2, ok | ok_o | hz_hit | ok_n

    old_state, new_state, hz_live, ok_s = jax.lax.cond(
        need.any(), fallback, lambda op: op,
        (old_state, new_state, hz_live, ok_s))

    ok = jnp.zeros((q,), jnp.bool_).at[order].set(ok_s[:q])
    return old_state, new_state, hz_live, ok
