"""Linear probing over per-query windows: the layout and the math.

A query's probe sequence ``h0, h0+1, ..., h0+max_probes-1`` (mod C) lies
inside the ``window_rows(max_probes)`` consecutive 128-slot rows that start
at row ``h0 // 128``.  ``windows`` gathers those rows for every query with
XLA row gathers (the table viewed as ``[C/128, 128]``: no copy when
``C % 128 == 0``), so each probe round of the loop becomes one
lane-parallel compare over a dense ``[Q, W]`` window, and "the first LIVE
match before the first EMPTY" is two lane-min reductions.  The window
always covers the probe bound: nothing escapes, whatever the table size or
the hash skew.

Both op sets of the linear backend run this math: ``core/buckets.py``
evaluates ``probe``/``claim`` as plain XLA, ``kernels/probe.py`` runs the
same functions inside Pallas kernels.  ``insert`` is the insert protocol
both share, with the claim pass passed in; ``insert_either`` runs the same
protocol on one of two tables chosen by a traced flag, with no whole table
through a conditional.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

I32 = jnp.int32
EMPTY, LIVE, TOMB, MIGRATED = 0, 1, 2, 3
LANES = 128   # slots per table row


def window_rows(max_probes: int) -> int:
    """Table rows a probe window spans: ``h0 % 128 + max_probes`` slots."""
    return -(-(LANES - 1 + max_probes) // LANES)


def row_view(a: jax.Array, c: int, max_probes: int) -> jax.Array:
    """The table as rows of ``LANES`` slots in which window row ``r`` (taken
    mod the row count) holds slots ``(r * LANES + j) % c``.  A free reshape
    when ``c % LANES == 0``; otherwise (tables under 128 slots with
    power-of-two sizing) a small wrapped copy."""
    if c % LANES == 0:
        return a.reshape(c // LANES, LANES)
    n = (-(-c // LANES) + window_rows(max_probes)) * LANES
    return jnp.tile(a, -(-n // c))[:n].reshape(-1, LANES)


def windows(arrays, h0: jax.Array, c: int, max_probes: int):
    """Each query's probe window of every array, [Q, W]: lane ``j`` holds
    slot ``(h0 - h0 % LANES + j) % c``."""
    nrows = window_rows(max_probes)
    out = []
    for a in arrays:
        v = row_view(a, c, max_probes)
        rows = (h0[:, None] // LANES
                + jnp.arange(nrows, dtype=I32)[None, :]) % v.shape[0]
        out.append(v[rows].reshape(h0.shape[0], nrows * LANES))
    return out


def slot(h0: jax.Array, lane: jax.Array) -> jax.Array:
    """Window lane -> unwrapped slot (``>= h0``; ``% C`` is physical)."""
    return h0 - h0 % LANES + lane


def first(mask, pos, none: int):
    """Smallest ``pos`` where ``mask`` holds, per row; ``none`` if nowhere."""
    return jnp.min(jnp.where(mask, pos, none), axis=1, keepdims=True)


def probe(kw, sw, off, qk, max_probes: int):
    """One query per row: ``kw``/``sw`` [Q, W] key/state windows, ``off``
    [Q, 1] the start slot's lane, ``qk`` [Q, 1] the key.  Returns (hit,
    stop, pos, inwin): the lane of the LIVE match met before the first
    EMPTY (-1 = absent), the first EMPTY lane of the probe range (W if
    none), and the lane index / probe-range masks."""
    w = kw.shape[1]
    pos = jax.lax.broadcasted_iota(I32, kw.shape, 1)
    inwin = (pos >= off) & (pos < off + max_probes)
    stop = first(inwin & (sw == EMPTY), pos, w)
    hit = first(inwin & (pos < stop) & (sw == LIVE) & (kw == qk), pos, w)
    return jnp.where(hit < w, hit, -1), stop, pos, inwin


def claim(kw, sw, off, qk, max_probes: int):
    """The insert's claim pass: (hit, free) lanes, the presence probe and
    the first non-LIVE lane of the probe range (claim-first-non-LIVE);
    -1 = none."""
    hit, _, pos, inwin = probe(kw, sw, off, qk, max_probes)
    w = sw.shape[1]
    free = first(inwin & (sw != LIVE), pos, w)
    return hit, jnp.where(free < w, free, -1)


def dense_claim(tkey, tstate, h0, keys, max_probes: int):
    """``claim`` evaluated by XLA over gathered windows; [Q] lanes."""
    kw, sw = windows((tkey, tstate), h0, tkey.shape[0], max_probes)
    hit, free = claim(kw, sw, (h0 % LANES)[:, None], keys[:, None],
                      max_probes)
    return hit[:, 0], free[:, 0]


def settle(phys: jax.Array, dist: jax.Array, c: int) -> jax.Array:
    """Which claims keep their slot (``phys``; ``c`` = no claim): of the
    claims on one slot, the one nearest its start slot (``dist``), then
    the lowest batch index — the order in which a probe-by-probe insert
    would reach the slot.  ONE sort."""
    q = phys.shape[0]
    ps, _, order = jax.lax.sort((phys, dist, jnp.arange(q, dtype=I32)),
                                num_keys=3)
    head = jnp.concatenate([jnp.ones((1,), bool), ps[1:] != ps[:-1]])
    return jnp.zeros((q,), bool).at[order].set(head & (ps < c))


def insert(tkey, tval, tstate, h0, keys, vals, mask, max_probes: int,
           claim_pass=dense_claim):
    """Batched insert (set semantics): every masked key absent from its
    probe range claims the first non-LIVE slot of it; claims on one slot go
    to the claimant nearest its start slot, then to the lowest batch index
    (``settle``), and the others claim again on the updated table until
    each has a slot or its range is full.

    Caller contract: ``mask`` is winner-filtered (distinct keys), so a key
    cannot become present while it waits.  ``claim_pass(tkey, tstate, h0,
    keys, max_probes)`` returns the (hit, free) lanes — XLA or a kernel.
    Returns (tkey', tval', tstate', ok[Q])."""
    c = tkey.shape[0]

    def body(carry):
        t, pending, ok = carry
        hit, free = claim_pass(t[0], t[2], h0, keys, max_probes)
        want, won, wp = _round(hit, free, h0, pending, c, c)
        return _put(t, wp, keys, vals), want & ~won, ok | won

    t, _, ok = jax.lax.while_loop(
        lambda carry: carry[1].any(), body,
        ((tkey, tval, tstate), mask, jnp.zeros(keys.shape, bool)))
    return (*t, ok)


def insert_either(a, b, into_b, h0, keys, vals, mask, max_probes,
                  claim_pass=dense_claim):
    """``insert`` into table ``b`` where the scalar ``into_b`` holds, else
    into ``a``: ``a`` and ``b`` are (key, val, state) triples, of sizes that
    may differ, ``max_probes`` their pair of probe bounds and ``h0`` the
    target's start slots.

    One claim loop carries both tables.  Each round's claim pass reads the
    target inside a ``lax.cond`` whose outputs are the [Q] lanes alone, and
    each table's scatters sit in a loop of one round for the target and
    none for the other (``_put_if``).  So no whole table passes through a
    conditional branch that hands it on unwritten, which is where XLA
    inserts whole-table copies, and the other table costs no scatter: on
    the TPU a scatter whose indices are all out of range costs as much as
    one that writes.  Returns (a', b', ok[Q])."""
    ca, cb = a[0].shape[0], b[0].shape[0]
    c = jnp.where(into_b, cb, ca)
    none = max(ca, cb)

    def body(carry):
        a, b, pending, ok = carry
        hit, free = jax.lax.cond(
            into_b,
            lambda: claim_pass(b[0], b[2], h0, keys, max_probes[1]),
            lambda: claim_pass(a[0], a[2], h0, keys, max_probes[0]))
        want, won, wp = _round(hit, free, h0, pending, c, none)
        return (_put_if(~into_b, a, wp, keys, vals),
                _put_if(into_b, b, wp, keys, vals), want & ~won, ok | won)

    a, b, _, ok = jax.lax.while_loop(
        lambda carry: carry[2].any(), body,
        (a, b, mask, jnp.zeros(keys.shape, bool)))
    return a, b, ok


def _round(hit, free, h0, pending, c, none: int):
    """One claim round: the pending keys absent from the table with a free
    lane (``want``), those that keep their slot (``won``, ``settle``) and
    the slots they write (``none``, out of range, elsewhere)."""
    want = pending & (hit < 0) & (free >= 0)
    phys = jnp.where(want, slot(h0, free) % c, none)
    won = settle(phys, free - h0 % LANES, none)
    return want, won, jnp.where(won, phys, none)


def _put_if(on, t, idx, keys, vals):
    """``_put`` where the scalar ``on`` holds, as a loop of at most one
    round: the table stays in place either way, with no conditional."""
    return jax.lax.while_loop(
        lambda c: c[1], lambda c: (_put(c[0], idx, keys, vals), False),
        (t, on))[0]


def _put(t, idx, keys, vals):
    """Write ``keys``/``vals`` LIVE into the (key, val, state) triple ``t``
    at ``idx``; out-of-range indices are dropped."""
    k, v, s = t
    return (k.at[idx].set(keys, mode="drop"), v.at[idx].set(vals, mode="drop"),
            s.at[idx].set(LIVE, mode="drop"))


def retry_share(tkey, tstate, h0, keys, mask, max_probes: int,
                claim_pass=dense_claim):
    """Share of a masked insert batch whose first claim lost its slot
    (``settle``), i.e. that ``insert`` sends through a second claim
    pass."""
    c = tkey.shape[0]
    hit, free = claim_pass(tkey, tstate, h0, keys, max_probes)
    want = mask & (hit < 0) & (free >= 0)
    phys = jnp.where(want, slot(h0, free) % c, c)
    lost = want & ~settle(phys, free - h0 % LANES, c)
    return lost.sum() / jnp.maximum(mask.sum(), 1)
