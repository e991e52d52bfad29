"""Pure-jnp oracles for the Pallas kernels (the ground truth in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hashing

I32 = jnp.int32
EMPTY, LIVE, TOMB, MIGRATED = 0, 1, 2, 3


def probe_lookup_ref(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                     h0: jax.Array, qkey: jax.Array, max_probes: int):
    """Linear-probe lookup oracle.

    Probes slots h0, h0+1, ... (mod C): stop on LIVE match (found) or EMPTY
    (absent); skip TOMB/MIGRATED.  Returns (found[Q] bool, val[Q] i32).
    """
    c = tkey.shape[0]
    q = qkey.shape[0]

    def body(i, carry):
        active, found, val = carry
        pos = (h0 + i) % c
        st = tstate[pos]
        hit = active & (st == LIVE) & (tkey[pos] == qkey)
        stop = active & (st == EMPTY)
        val = jnp.where(hit, tval[pos], val)
        found = found | hit
        active = active & ~hit & ~stop
        return active, found, val

    init = (jnp.ones((q,), bool), jnp.zeros((q,), bool), jnp.zeros((q,), I32))
    _, found, val = jax.lax.fori_loop(0, max_probes, body, init)
    return found, val


def probe_insert_ref(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                     h0: jax.Array, keys: jax.Array, vals: jax.Array,
                     mask: jax.Array, max_probes: int):
    """Linear-probe insert oracle on raw table arrays, one probe step at a
    time (claim-first-non-LIVE; every pending key tries the slot at the same
    probe distance, the lowest batch index wins a contested slot).
    ``window.insert`` claims whole probe ranges at once and settles a
    contested slot by probe distance, then batch index, which is the order
    in which this loop reaches it: both build the same table, slot for slot
    (``tests/test_kernels.py::test_linear_insert_tables_match_oracle``).

    Caller contract: ``mask`` is winner-filtered (at most one True per
    distinct key; use ``buckets.batch_winners``).  Returns
    (tkey', tval', tstate', ok[Q]).
    """
    c = tkey.shape[0]
    q = keys.shape[0]
    present, _ = probe_lookup_ref(tkey, tval, tstate, h0, keys, max_probes)
    pending0 = mask & ~present
    idx = jnp.arange(q, dtype=I32)

    def body(p, carry):
        key, val, state, pending, done = carry
        pos = (h0 + p) % c
        free = pending & (state[pos] != LIVE)
        wpos = jnp.where(free, pos, c)
        claim = jnp.full((c,), q, I32).at[wpos].min(idx, mode="drop")
        won = free & (claim[pos] == idx)
        wp = jnp.where(won, pos, c)
        key = key.at[wp].set(keys, mode="drop")
        val = val.at[wp].set(vals, mode="drop")
        state = state.at[wp].set(LIVE, mode="drop")
        return key, val, state, pending & ~won, done | won

    init = (tkey, tval, tstate, pending0, jnp.zeros((q,), bool))
    tkey, tval, tstate, _, done = jax.lax.fori_loop(0, max_probes, body, init)
    return tkey, tval, tstate, done


def ordered_lookup_ref(old_t, new_t, hazard_key, hazard_val, hazard_live,
                       h0_old, h0_new, qkey, max_probes: int):
    """The paper's ordered three-way check: old -> hazard -> new."""
    f_old, v_old = probe_lookup_ref(*old_t, h0_old, qkey, max_probes)
    eq = (qkey[:, None] == hazard_key[None, :]) & hazard_live[None, :]
    f_hz = eq.any(-1)
    v_hz = jnp.take(hazard_val, jnp.argmax(eq, axis=-1))
    f_new, v_new = probe_lookup_ref(*new_t, h0_new, qkey, max_probes)
    found = f_old | f_hz | f_new
    val = jnp.where(f_old, v_old, jnp.where(f_hz, v_hz, v_new))
    return found, val


def probe_delete_ref(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                     h0: jax.Array, keys: jax.Array, mask: jax.Array,
                     max_probes: int):
    """Linear-probe delete oracle: tombstone the LIVE slot holding each
    masked key (probe from h0, skip TOMB/MIGRATED, stop at EMPTY).

    Caller contract: ``mask`` is winner-filtered (at most one True per
    distinct key).  Returns (tstate', ok[Q]).
    """
    c = tkey.shape[0]
    q = keys.shape[0]

    def body(i, carry):
        active, found, loc = carry
        pos = (h0 + i) % c
        st = tstate[pos]
        hit = active & (st == LIVE) & (tkey[pos] == keys)
        stop = active & (st == EMPTY)
        loc = jnp.where(hit, pos, loc)
        found = found | hit
        active = active & ~hit & ~stop
        return active, found, loc

    init = (jnp.ones((q,), bool), jnp.zeros((q,), bool),
            jnp.full((q,), -1, I32))
    _, found, loc = jax.lax.fori_loop(0, max_probes, body, init)
    ok = mask & found
    tstate = tstate.at[jnp.where(ok, loc, c)].set(TOMB, mode="drop")
    return tstate, ok


def tc_row_lookup_ref(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                      rows: jax.Array, qkey: jax.Array):
    """Single-row twochoice lookup oracle: gather row ``rows[e]`` and match
    all W lanes.  Returns (found[E], val[E], loc[E] flat slot or -1)."""
    w = tkey.shape[1]
    krow, vrow, srow = tkey[rows], tval[rows], tstate[rows]   # [E, W]
    hit = (krow == qkey[:, None]) & (srow == LIVE)
    found = hit.any(-1)
    lane = jnp.argmax(hit, axis=-1)
    val = jnp.take_along_axis(vrow, lane[:, None], axis=-1)[:, 0]
    return (found, jnp.where(found, val, 0),
            jnp.where(found, rows * w + lane.astype(I32), -1))


def tc_insert_ref(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                  rows_a: jax.Array, rows_b: jax.Array, keys: jax.Array,
                  vals: jax.Array, mask: jax.Array, max_rounds: int):
    """Twochoice insert oracle on raw [B, W] arrays: alternate the two row
    choices per round, claim the row's first non-LIVE lane, lowest batch
    index wins a contested lane (same linearization as
    ``buckets.twochoice_insert``).

    Caller contract: ``mask`` is winner-filtered.  Returns
    (tkey', tval', tstate', ok[Q]).
    """
    b, w = tkey.shape
    q = keys.shape[0]
    fa, _, _ = tc_row_lookup_ref(tkey, tval, tstate, rows_a, keys)
    fb, _, _ = tc_row_lookup_ref(tkey, tval, tstate, rows_b, keys)
    pending0 = mask & ~(fa | fb)
    idx = jnp.arange(q, dtype=I32)
    nslots = b * w

    def body(r, carry):
        key, val, state, pending, done = carry
        bkt = jnp.where(r % 2 == 0, rows_a, rows_b)
        row_free = state[bkt] != LIVE                       # [Q, W]
        has_free = pending & row_free.any(-1)
        lane = jnp.argmax(row_free, axis=-1)
        flat = bkt * w + lane.astype(I32)
        wflat = jnp.where(has_free, flat, nslots)
        claim = jnp.full((nslots,), q, I32).at[wflat].min(idx, mode="drop")
        won = has_free & (claim[flat % nslots] == idx) & (wflat < nslots)
        wp = jnp.where(won, flat, nslots)
        key = key.reshape(-1).at[wp].set(keys, mode="drop").reshape(b, w)
        val = val.reshape(-1).at[wp].set(vals, mode="drop").reshape(b, w)
        state = state.reshape(-1).at[wp].set(LIVE, mode="drop").reshape(b, w)
        return key, val, state, pending & ~won, done | won

    init = (tkey, tval, tstate, pending0, jnp.zeros((q,), bool))
    tkey, tval, tstate, _, done = jax.lax.fori_loop(0, max_rounds, body, init)
    return tkey, tval, tstate, done


def chain_lookup_ref(akey: jax.Array, aval: jax.Array, astate: jax.Array,
                     anext: jax.Array, heads: jax.Array, b: jax.Array,
                     qkey: jax.Array, max_chain: int):
    """Pointer-chasing chain lookup oracle: lock-step batched traversal from
    ``heads[b]`` along ``anext``, bounded by ``max_chain`` hops — each hop is
    one dependent arena gather (the CPU cost model the arena-sorted fused
    path exists to avoid).  Returns (found[Q], val[Q], loc[Q] node or -1).
    """
    q = qkey.shape[0]

    def body(_, carry):
        cur, found, val, loc = carry
        valid = cur >= 0
        c = jnp.where(valid, cur, 0)
        hit = valid & (astate[c] == LIVE) & (akey[c] == qkey) & ~found
        val = jnp.where(hit, aval[c], val)
        loc = jnp.where(hit, cur, loc)
        found = found | hit
        step = valid & ~found
        cur = jnp.where(step, anext[c], jnp.where(found, cur, -1))
        return cur, found, val, loc

    init = (heads[b], jnp.zeros((q,), bool), jnp.zeros((q,), I32),
            jnp.full((q,), -1, I32))
    _, found, val, loc = jax.lax.fori_loop(0, max_chain, body, init)
    return found, val, loc


def chain_delete_ref(akey: jax.Array, aval: jax.Array, astate: jax.Array,
                     anext: jax.Array, heads: jax.Array, b: jax.Array,
                     keys: jax.Array, mask: jax.Array, max_chain: int):
    """Pointer-chasing chain delete oracle: traverse, then tombstone the
    node holding each masked key (logical deletion; reclamation is the
    compaction pass).  Caller contract: mask winner-filtered.  Returns
    (astate', ok[Q])."""
    n = akey.shape[0]
    found, _, loc = chain_lookup_ref(akey, aval, astate, anext, heads, b,
                                     keys, max_chain)
    ok = mask & found
    astate = astate.at[jnp.where(ok, loc, n)].set(TOMB, mode="drop")
    return astate, ok


def chain_insert_ref(akey, aval, astate, anext, heads, free_stack, free_top,
                     b, keys, vals, mask, max_chain: int):
    """Pointer-chasing chain insert oracle on raw arena arrays: presence by
    lock-step traversal, want-rank tail allocation, insert-at-head linking
    in original-index order — the same linearization, node placement, and
    pointer structure as ``buckets.chain_insert``.

    Caller contract: ``mask`` is winner-filtered.  Returns
    (akey', aval', astate', anext', heads', free_top', ok[Q]).
    """
    n = akey.shape[0]
    nb = heads.shape[0]
    q = keys.shape[0]
    present, _, _ = chain_lookup_ref(akey, aval, astate, anext, heads, b,
                                     keys, max_chain)
    want = mask & ~present
    rank = jnp.cumsum(want.astype(I32)) - 1
    can = want & (rank < free_top)
    node = free_stack[jnp.where(can, free_top - 1 - rank, 0)]
    wnode = jnp.where(can, node, n)
    akey = akey.at[wnode].set(keys, mode="drop")
    aval = aval.at[wnode].set(vals, mode="drop")
    astate = astate.at[wnode].set(LIVE, mode="drop")
    idx = jnp.arange(q, dtype=I32)
    sortkey = jnp.where(can, b, nb)
    order = jnp.lexsort((idx, sortkey))
    sb, snode, scan = sortkey[order], node[order], can[order]
    nxt_same = jnp.concatenate([snode[1:], jnp.full((1,), -1, I32)])
    same_bucket = jnp.concatenate([sb[1:] == sb[:-1], jnp.zeros((1,), bool)])
    old_head = heads[jnp.where(scan, sb, 0)]
    nxt = jnp.where(same_bucket, nxt_same, jnp.where(scan, old_head, -1))
    anext = anext.at[jnp.where(scan, snode, n)].set(nxt, mode="drop")
    is_start = jnp.concatenate([jnp.ones((1,), bool), sb[1:] != sb[:-1]])
    heads = heads.at[jnp.where(scan & is_start, sb, nb)].set(snode,
                                                             mode="drop")
    free_top = free_top - jnp.sum(can.astype(I32))
    return akey, aval, astate, anext, heads, free_top, can


def chain_ordered_lookup_ref(old_arena, old_links, new_arena, new_links,
                             hazard_key, hazard_val, hazard_live,
                             b_old, b_new, qkey, max_chain: int):
    """The paper's ordered three-way check over chained tables:
    old chains -> hazard buffer -> new chains."""
    f_old, v_old, _ = chain_lookup_ref(*old_arena, *old_links, b_old, qkey,
                                       max_chain)
    eq = (qkey[:, None] == hazard_key[None, :]) & hazard_live[None, :]
    f_hz = eq.any(-1)
    v_hz = jnp.take(hazard_val, jnp.argmax(eq, axis=-1))
    f_new, v_new, _ = chain_lookup_ref(*new_arena, *new_links, b_new, qkey,
                                       max_chain)
    found = f_old | f_hz | f_new
    val = jnp.where(f_old, v_old, jnp.where(f_hz, v_hz, v_new))
    return found, val


def tc_delete_ref(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                  rows_a: jax.Array, rows_b: jax.Array, keys: jax.Array,
                  mask: jax.Array):
    """Twochoice delete oracle: tombstone the LIVE lane holding each masked
    key in either row.  Caller contract: mask winner-filtered.  Returns
    (tstate', ok[Q])."""
    b, w = tkey.shape
    fa, _, la = tc_row_lookup_ref(tkey, tval, tstate, rows_a, keys)
    fb, _, lb = tc_row_lookup_ref(tkey, tval, tstate, rows_b, keys)
    ok = mask & (fa | fb)
    loc = jnp.where(fa, la, lb)
    tstate = tstate.reshape(-1).at[jnp.where(ok, loc, b * w)].set(
        TOMB, mode="drop").reshape(b, w)
    return tstate, ok


def cuckoo_kick_ref(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                    rows_a: jax.Array, rows_b: jax.Array,
                    hfn_a, hfn_b, nbuckets: int,
                    keys: jax.Array, vals: jax.Array, pending: jax.Array,
                    max_kick: int):
    """Batched bounded kick-out over the cuckoo table's [2B, W] rows
    (side A rows [0, B), side B rows [B, 2B); ``rows_a``/``rows_b`` are the
    two candidate rows of each query, already side-offset).

    Runs exactly ``max_kick`` fixed iterations (the MAX_KICK_OUT idiom of
    SNIPPETS.md snippet 1, rendered as a batched fori_loop).  Per iteration
    each still-pending query forms one of two plans:

    * **plan A** — either candidate row has a free lane: claim it (prefer
      the a-row, matching the insert tie-break);
    * **plan B** — both rows full: pick a victim lane whose occupant's
      ALTERNATE row (the other side, under the other hash function)
      currently has a free lane, move the victim there, and take its lane.
      The candidate scan order is rotated by the iteration index so two
      queries fighting over the same rows do not ping-pong on one victim.

    Arbitration is per-ROW: a scatter-min lock over all 2B rows (lowest
    batch index wins); a query executes only if it owns every row its plan
    touches (one row for plan A, victim row + alternate row for plan B).
    Losers simply retry next iteration — the conflict-escape.  Because a
    resident entry only ever moves INTO a directly-free lane of its own
    alternate row, no resident is ever evicted without a landing slot: on
    kick exhaustion only the NEW key reports ok=False.

    Caller contract: ``pending`` is winner-filtered and presence-checked.
    Returns (tkey', tval', tstate', done[Q]).
    """
    b2, w = tkey.shape
    q = keys.shape[0]
    idx = jnp.arange(q, dtype=I32)
    lane_ids = jnp.arange(2 * w, dtype=I32)
    nslots = b2 * w

    def body(it, carry):
        key, val, state, pend, done = carry
        sa, sb = state[rows_a], state[rows_b]              # [Q, W]
        free_a, free_b = (sa != LIVE).any(-1), (sb != LIVE).any(-1)

        # plan A: direct claim of a free lane (a-row priority)
        plan_a = pend & (free_a | free_b)
        row_a_tgt = jnp.where(free_a, rows_a, rows_b)
        tgt_free = state[row_a_tgt] != LIVE                # [Q, W]
        lane_a = jnp.argmax(tgt_free, axis=-1)

        # plan B: move a victim whose alternate row has a free lane.
        # victim candidates are the 2W lanes (a-row lanes then b-row lanes);
        # a victim parked in side A relocates to B + hb(victim), side B to
        # ha(victim) — always the other side, so victim row != alt row.
        vrow = jnp.concatenate([
            jnp.broadcast_to(rows_a[:, None], (q, w)),
            jnp.broadcast_to(rows_b[:, None], (q, w))], axis=-1)  # [Q, 2W]
        vkey = key[vrow, lane_ids % w]                     # [Q, 2W]
        alt_a = nbuckets + hashing.bucket_of(hfn_b, vkey, nbuckets)
        alt_b = hashing.bucket_of(hfn_a, vkey, nbuckets)
        valt = jnp.where(lane_ids[None, :] < w, alt_a, alt_b)
        cand = (state[vrow, lane_ids % w] == LIVE) \
            & (state[valt] != LIVE).any(-1)                # [Q, 2W]
        rot = (lane_ids + it) % (2 * w)
        sel = rot[jnp.argmax(jnp.take_along_axis(
            cand, jnp.broadcast_to(rot[None, :], (q, 2 * w)), axis=-1),
            axis=-1)]
        plan_b = pend & ~plan_a & cand.any(-1)
        b_vrow = jnp.take_along_axis(vrow, sel[:, None], axis=-1)[:, 0]
        b_valt = jnp.take_along_axis(valt, sel[:, None], axis=-1)[:, 0]
        b_vlane = sel % w
        b_vkey = jnp.take_along_axis(vkey, sel[:, None], axis=-1)[:, 0]

        # per-row locks: a query owns a row iff it wins the scatter-min on
        # it; plan A needs its target row, plan B both victim + alt rows
        lock = jnp.full((b2,), q, I32)
        lock = lock.at[jnp.where(plan_a, row_a_tgt, b2)].min(idx, mode="drop")
        lock = lock.at[jnp.where(plan_b, b_vrow, b2)].min(idx, mode="drop")
        lock = lock.at[jnp.where(plan_b, b_valt, b2)].min(idx, mode="drop")
        own_a = plan_a & (lock[row_a_tgt % b2] == idx)
        own_b = plan_b & (lock[b_vrow % b2] == idx) & (lock[b_valt % b2] == idx)

        # plan B execution: victim lands in its alternate row's first free
        # lane, then the new key takes the vacated lane
        alt_lane = jnp.argmax(state[b_valt] != LIVE, axis=-1)
        b_vval = val[b_vrow, b_vlane]
        mv = jnp.where(own_b, b_valt * w + alt_lane, nslots)
        key = key.reshape(-1).at[mv].set(b_vkey, mode="drop")
        val = val.reshape(-1).at[mv].set(b_vval, mode="drop")
        state = state.reshape(-1).at[mv].set(LIVE, mode="drop")

        won = own_a | own_b
        wp = jnp.where(own_a, row_a_tgt * w + lane_a,
                       jnp.where(own_b, b_vrow * w + b_vlane, nslots))
        key = key.at[wp].set(keys, mode="drop").reshape(b2, w)
        val = val.at[wp].set(vals, mode="drop").reshape(b2, w)
        state = state.at[wp].set(LIVE, mode="drop").reshape(b2, w)
        return key, val, state, pend & ~won, done | won

    init = (tkey, tval, tstate, pending, jnp.zeros((q,), bool))
    tkey, tval, tstate, _, done = jax.lax.fori_loop(0, max_kick, body, init)
    return tkey, tval, tstate, done
