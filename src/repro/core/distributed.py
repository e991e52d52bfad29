"""Distributed DHash: the table sharded over a mesh axis.

Ownership is by a *fixed* owner hash (never rebuilt): shard s owns key k iff
``owner_hash(k) % S == s``.  Rebuilds swap each shard's *local* hash function;
because every shard executes the same transition stream (SPMD), the epoch
swap is collectively synchronized for free — the multi-host analogue of the
paper's ``synchronize_rcu`` grace period.

Query routing is one all_to_all pair (there and back), the same dispatch
pattern as MoE token routing.  The send-buffer layout is a **two-pass
counting sort** (HashGraph's idiom): pass 1 histograms keys per owner and
ranks each key within its owner; pass 2 scatters keys into exactly-sized
per-owner segments of a ``[S, cap]`` buffer.  With a fixed per-owner cap
the exclusive prefix sum over the capped histogram is the affine map
``base[s] = s * cap`` — i.e. the row offsets of the 2-D buffer — so no
argsort is ever needed: the router contributes ZERO ``sort`` primitives
and the owner-grouped buffer feeds the fused kernels' own bucket sort
directly (a routed fused ``stack_lookup`` stays at ONE sort + ONE
pallas_call total, the same budget as an unrouted op).

``cap=None`` (baseline) uses cap=Q — overflow-proof even under a fully
adversarial key set (every key owned by one shard — the paper's collision
attack) at S x the wire bytes.  The capped path uses
``cap = ceil(c·Q/S)`` plus a **two-level spill slab**: keys past an
owner's cap are re-routed — in the SAME single pass — into ``spill_cap``
extra columns of the same buffer, shared across owners by global spill
rank (HashGraph's counting layout applied one level down: the exact
histogram already sizes the overflow region, so no second dispatch is
ever needed).  Because total spill over any batch is bounded by
``Q - cap`` (k overflowing owners spill at most ``Q - k*cap`` keys),
``spill_cap = Q - cap`` makes the capped layout overflow-PROOF; smaller
slabs (``route_spill_cap`` with a ``slack`` budget) trade width for an
exactly-accounted ``dropped`` count — keys beyond primary+slab are
reported per owner, never silently lost.  The cond-gated full-width
retry this replaces is gone from the contract: a spilling batch costs
the same ONE routed op as a balanced one.

These functions are written to be called INSIDE ``jax.shard_map`` with the
table sharded (one leaf-shard per device along ``axis``) and queries sharded
along their batch dim.  Every shard-local table op dispatches through the
``BucketBackend`` descriptor registry (core/backend.py), so any registered
backend — fused or jnp — shards without changes here.

**The served path is exact.**  ``routed_service_step`` (the step
``DHashEngine`` runs on a sharded stack) routes every key of any key set
to its owner.  Its inserts and deletes go at the full width Q per owner
row.  The batch is split into contiguous per-device slices and the
exchange keeps source order (row j of the received buffer came from
device j, each row in batch order), so the flattened buffer an owner
serves is its keys in global batch order: the earlier of two copies of a
key wins, as on one chip.  The price is padding: an owner probes S·Q
insert or delete lanes for about Q keys.  The lookup writes nothing and
its answers do not depend on the order of its probes, so it goes in
**rounds** of ``lookup_cap(Q, S)`` columns per owner row (a share plus a
binomial margin): round r carries the keys ranked ``[r·cap, (r+1)·cap)``
within their owner, and every device runs the round count that the
busiest (sender, owner) pair needs.  Balanced owners take one round of
S·cap lanes; a batch all owned by one shard takes ``ceil(Q / cap)``.
A capped routed op rides the spill slab (overflow-proof by default, the
same width Q); only a compact slab can drop keys, and the op returns its
``Route`` so the caller sees ``dropped``.

Named scopes: the layout work (histogram, ranks, the send-buffer scatters,
the unroute gathers) runs under ``dhash.route``, the ``all_to_all``s under
``dhash.exchange``, beside ``core/dhash.py``'s operation scopes.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import dhash, hashing

I32 = jnp.int32


class Route(NamedTuple):
    """The routing layout of one batch: the [S, cap + spill_cap] send
    buffer (primary columns + shared spill-slab columns) plus the per-key
    coordinates that invert it, and exact overflow/drop accounting."""
    send: jax.Array      # [S, cap + spill_cap] keys: owner-grouped primary
                         # columns, then slab columns shared by spill rank
    smask: jax.Array     # [S, cap + spill_cap] bool: slot carries a key
    owner: jax.Array     # [Q] i32 owner of each key (batch order)
    rank: jax.Array      # [Q] i32 arrival rank within its owner (stable)
    kept: jax.Array      # [Q] bool: rank < cap (primary columns)
    overflow: jax.Array  # [S] i32 EXACT per-owner spill: max(hist - cap, 0)
    cap: int             # static primary width
    spill_cap: int       # static slab width
    spill_rank: jax.Array  # [Q] i32 global rank among spilled keys (stable;
                           # meaningless where ``kept``)
    served: jax.Array    # [Q] bool: kept | (spilled & spill_rank < spill_cap)
    slab_owner: jax.Array  # [spill_cap] i32 explicit owner id of each slab
                           # column (-1: column empty this batch)
    dropped: jax.Array   # [S] i32 EXACT per-owner keys beyond primary+slab
    hist: jax.Array      # [S] i32 EXACT keys per owner (the owner's load)


def route_cap(cap_factor: float, q: int, nshards: int) -> int:
    """The capped-dispatch buffer width ``cap = ceil(c·Q/S)``, clamped to
    [1, Q].  ``cap_factor <= 0`` means the overflow-proof full width.

    The ceil is taken on the full product ``c·Q/S`` (``math.ceil``, the one
    place this is computed) — truncating the float product to int first
    (the old ``int(c*q)`` idiom) understates the cap by 1 whenever the
    product carries a fractional part into the division."""
    if cap_factor <= 0:
        return q
    return min(q, max(1, math.ceil(cap_factor * q / nshards)))


def lookup_cap(q: int, nshards: int) -> int:
    """Columns per owner row of a lookup round: a sender's share ``Q/S``
    plus eight standard deviations of a uniform owner hash's count
    (``8·sqrt(Q/S)``, a binomial margin), clamped to [1, Q].  Balanced
    batches fit one round; the rounds serve any overflow."""
    share = q / nshards
    return min(q, max(1, math.ceil(share + 8 * math.sqrt(share))))


def route_spill_cap(q: int, cap: int, slack: float | None = None) -> int:
    """Spill-slab width for a [Q] batch routed at ``cap`` per owner.

    Total spill over ANY batch is bounded by ``Q - cap``: if k owners
    overflow they keep ``k*cap`` keys in primary columns, spilling at most
    ``Q - k*cap <= Q - cap`` (k >= 1).  So the default (``slack=None``)
    returns ``Q - cap`` — an overflow-PROOF slab: every spilled key of
    every possible batch lands in the buffer and the router never drops.

    A ``slack`` budget in (0, 1) sizes a compact slab ``ceil(slack·Q)``
    instead (clamped to the overflow-proof bound): width shrinks to
    ``cap + slack·Q``, and keys whose global spill rank exceeds the slab
    are counted EXACTLY in ``Route.dropped`` — callers choosing a compact
    slab observe every key it cannot carry.  ``slack >= 1`` is the
    overflow-proof bound again; ``slack <= 0`` disables the slab (pure
    capped layout)."""
    worst = max(q - cap, 0)
    if slack is None:
        return worst
    if slack <= 0:
        return 0
    return min(worst, math.ceil(slack * q))


@jax.named_scope("dhash.route")
def _route(keys: jax.Array, owner: jax.Array, nshards: int,
           cap: int | None = None, spill_cap: int = 0) -> Route:
    """Group keys by owner into a [S, cap + spill_cap] send buffer — a
    two-level single-pass counting sort, no ``sort`` primitive:

    * pass 1: per-owner histogram + stable rank-within-owner via a running
      one-hot count (O(Q·S) vectorized work, the MoE dispatch idiom —
      cheap for mesh/tenant-scale S, and it removes the router's argsort
      from every routed op's budget), plus a global rank among spilled
      keys (one more cumsum) for the slab;
    * pass 2: ONE scatter places key i at column ``rank[i]`` of its
      owner's row if ``rank < cap`` (primary), else at column
      ``cap + spill_rank[i]`` (slab).  Slab columns are SHARED across
      owners by global spill rank — the exact histogram bounds total
      spill at ``Q - cap``, so ``spill_cap = Q - cap`` (the
      ``route_spill_cap`` default) carries every possible overflow —
      and each slab column's owner is recorded in ``slab_owner``.

    Primary and slab are concatenated columns of ONE buffer, so a routed
    op on a spilling batch costs exactly what a balanced batch costs —
    there is no second pass to retry into.  Keys beyond primary+slab
    (compact slabs only) are NOT silently zeroed: ``served`` marks every
    key the buffer carries, ``overflow[s] = max(hist[s] - cap, 0)`` counts
    spill exactly, and ``dropped[s]`` counts the slab's exact per-owner
    shortfall."""
    q = keys.shape[0]
    cap = q if cap is None else cap
    spill_cap = 0 if cap >= q else min(spill_cap, q - cap)
    owner = owner.astype(I32)
    onehot = (owner[:, None] == jnp.arange(nshards, dtype=I32)[None, :]
              ).astype(I32)
    hist = onehot.sum(axis=0)                                     # [S]
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                               owner[:, None], axis=1)[:, 0]      # [Q]
    kept = rank < cap
    spilled = ~kept
    spill_rank = jnp.cumsum(spilled.astype(I32)) - 1              # [Q]
    served = kept | (spilled & (spill_rank < spill_cap))
    # primary keys land at their owner rank, spilled keys at the shared
    # slab column for their global spill rank; anything past the slab
    # scatters out of bounds and mode="drop" discards it
    col = jnp.where(kept, rank, cap + spill_rank)
    send = jnp.zeros((nshards, cap + spill_cap), keys.dtype).at[
        owner, col].set(keys, mode="drop")
    smask = jnp.zeros((nshards, cap + spill_cap), bool).at[
        owner, col].set(served, mode="drop")
    overflow = jnp.maximum(hist - cap, 0)
    slab_owner = jnp.full((spill_cap,), -1, I32).at[
        jnp.where(spilled, spill_rank, spill_cap)].set(owner, mode="drop")
    dropped = (onehot * (spilled & ~served).astype(I32)[:, None]).sum(axis=0)
    return Route(send, smask, owner, rank, kept, overflow,
                 cap, spill_cap, spill_rank, served, slab_owner, dropped,
                 hist)


def _route_col(rt: Route) -> jax.Array:
    """Per-key column in the [S, cap + spill_cap] layout (out of bounds for
    keys the buffer does not carry — pair with mode="drop" / ``served``)."""
    return jnp.where(rt.kept, rt.rank, rt.cap + rt.spill_rank)


@jax.named_scope("dhash.route")
def _route_payload(payload: jax.Array, rt: Route) -> jax.Array:
    """Scatter a per-key payload (values, masks) into the
    [S, cap + spill_cap] layout of a ``Route`` computed for the same batch
    — primary AND slab slots are populated; dropped keys (compact slabs
    only) stay zero.  Shared by the distributed router and the serving
    tenant router."""
    nshards, width = rt.send.shape
    return jnp.zeros((nshards, width), payload.dtype).at[
        rt.owner, _route_col(rt)].set(payload, mode="drop")


@jax.named_scope("dhash.route")
def _unroute(resp_local: jax.Array, rt: Route, fill=None) -> jax.Array:
    """Invert a ``Route`` for a [S, cap + spill_cap] response: gather each
    key's slot (primary or slab) back to batch order.  Dropped keys take
    ``fill`` — by default 0 for integer/bool responses and NaN for floats,
    so a dropped float payload can never be mistaken for a real 0.0
    value."""
    if fill is None:
        fill = jnp.nan if jnp.issubdtype(resp_local.dtype, jnp.floating) else 0
    gathered = resp_local[rt.owner, jnp.where(rt.served, _route_col(rt), 0)]
    return jnp.where(rt.served, gathered,
                     jnp.asarray(fill, resp_local.dtype))


def shard_of(keys: jax.Array, nshards: int,
             owner_hfn: hashing.HashFn) -> jax.Array:
    """Owning shard of each key under the FIXED (never-rebuilt) owner hash."""
    return (hashing.hash_u32(owner_hfn, keys) % jnp.uint32(nshards)).astype(I32)


def _owner_route(keys: jax.Array, nshards: int, owner_hfn: hashing.HashFn,
                 cap: int | None, spill_slack: float | None) -> Route:
    """The layout of a [Q] batch over its owner shards: full width without
    ``cap``; with it, ``cap`` columns plus the spill slab of
    ``route_spill_cap(Q, cap, spill_slack)``."""
    spill = 0 if cap is None else route_spill_cap(keys.shape[0], cap,
                                                  spill_slack)
    return _route(keys, shard_of(keys, nshards, owner_hfn), nshards, cap,
                  spill)


@jax.named_scope("dhash.exchange")
def _exchange(x: jax.Array, axis: str) -> jax.Array:
    """Row j of ``x`` to device j along ``axis``; row j of the result came
    from device j."""
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0)


def routed_lookup(d: dhash.DHashState, keys: jax.Array, axis: str,
                  owner_hfn: hashing.HashFn, cap: int | None = None,
                  spill_slack: float | None = None):
    """DHash lookup across shards.  Returns ``(found, vals, route,
    rounds)``.  Call inside shard_map.

    Without ``cap`` the lookup runs in rounds of ``lookup_cap(Q, S)``
    columns per owner row until every device has served every key
    (``rounds``, the same on every device); ``route`` is the batch's
    full-width route, whose owner ranks place each key in its round.
    With ``cap``, one exchange: keys past their owner's cap ride the
    spill slab, and the default slab is overflow-proof, so every key is
    answered.  Only a compact slab (``spill_slack`` in (0, 1)) drops keys;
    they come back not found and ``route.dropped`` counts them per
    owner."""
    s = lax.axis_size(axis)
    rt = _owner_route(keys, s, owner_hfn, cap, spill_slack)
    if cap is None:
        return _lookup_rounds(d, keys, rt, axis)
    c = rt.send.shape[1]
    rk = _exchange(rt.send, axis)
    rm = _exchange(rt.smask, axis)
    found, vals = dhash.lookup(d, rk.reshape(-1))
    found = found & rm.reshape(-1)
    rf = _exchange(found.reshape(s, c), axis)
    rv = _exchange(vals.reshape(s, c), axis)
    return (_unroute(rf, rt, fill=False).astype(bool),
            _unroute(rv, rt, fill=0), rt, jnp.int32(1))


def _lookup_rounds(d: dhash.DHashState, keys: jax.Array, rt: Route,
                   axis: str):
    """``routed_lookup`` without a cap: round r sends each owner the keys
    ranked ``[r·cap, (r+1)·cap)`` within it, at column ``rank - r·cap`` of
    an [S, cap] buffer, and gathers their answers into the [Q] outputs.
    The round count is agreed over ``axis`` (one ``pmax``), so every
    device runs the same collectives; a lookup writes nothing, so the
    answers are the full-width layout's."""
    s, q = rt.hist.shape[0], keys.shape[0]
    c = lookup_cap(q, s)
    with jax.named_scope("dhash.route"):
        rounds = lax.pmax((rt.hist.max() + c - 1) // c, axis)

    def one_round(r, out):
        with jax.named_scope("dhash.route"):
            col = rt.rank - r * c
            here = (col >= 0) & (col < c)
            at = jnp.where(here, col, c)       # out of bounds: dropped
            send = jnp.zeros((s, c), keys.dtype).at[rt.owner, at].set(
                keys, mode="drop")
            smask = jnp.zeros((s, c), bool).at[rt.owner, at].set(
                True, mode="drop")
        rk = _exchange(send, axis)
        rm = _exchange(smask, axis)
        found, vals = dhash.lookup(d, rk.reshape(-1))
        rf = _exchange((found & rm.reshape(-1)).reshape(s, c), axis)
        rv = _exchange(vals.reshape(s, c), axis)
        with jax.named_scope("dhash.route"):
            at = jnp.where(here, col, 0)
            return (jnp.where(here, rf[rt.owner, at], out[0]),
                    jnp.where(here, rv[rt.owner, at], out[1]))

    found, vals = lax.fori_loop(0, rounds, one_round,
                                (jnp.zeros((q,), bool), jnp.zeros((q,), I32)))
    return found, vals, rt, rounds


def routed_update(d: dhash.DHashState, keys: jax.Array, vals: jax.Array,
                  mask: jax.Array, axis: str, owner_hfn: hashing.HashFn,
                  op: Callable = dhash.insert, cap: int | None = None,
                  spill_slack: float | None = None):
    """DHash insert or delete (``op``) across shards.  Returns
    ``(d', ok, route)``.  Call inside shard_map.

    The layout is ``routed_lookup``'s: with the default slab every key is
    applied; under a compact slab a dropped key comes back ``ok=False``
    and ``route.dropped`` counts it.  ``vals`` travel for an insert
    only."""
    s = lax.axis_size(axis)
    rt = _owner_route(keys, s, owner_hfn, cap, spill_slack)
    c = rt.send.shape[1]
    rk = _exchange(rt.send, axis).reshape(-1)
    rm = _exchange(_route_payload(mask, rt), axis).reshape(-1)
    if op is dhash.insert:
        rv = _exchange(_route_payload(vals, rt), axis).reshape(-1)
        d, ok = op(d, rk, rv, rm)
    else:
        d, ok = op(d, rk, rm)
    rok = _exchange(ok.reshape(s, c), axis)
    return d, _unroute(rok, rt, fill=False).astype(bool), rt


# -- mesh x stack: the [S shards x T tenants] grid ---------------------------
#
# Owner of a key is the PAIR (shard_of(key), tenant): flat owner id
# ``shard * T + tenant`` routes through ONE capped all_to_all pair into
# per-shard tenant stacks.  Each shard holds a ``dhash.make_stack(T, ...)``
# whose per-tenant rebuild epochs stay fully independent (the stack ops
# don't change under routing); the received buffer is reshaped
# tenant-major so one vmapped stack op serves every (source shard, tenant)
# cell at once.  The router itself is sort-free, so the whole routed fused
# stack op keeps the single-op kernel budget: ONE sort + ONE pallas_call.


def grid_owner(keys: jax.Array, tenant: jax.Array, nshards: int,
               ntenants: int, owner_hfn: hashing.HashFn) -> jax.Array:
    """Flat [S·T] owner id of each key: ``shard_of(key) * T + tenant``."""
    return shard_of(keys, nshards, owner_hfn) * ntenants + tenant.astype(I32)


def _grid_exchange(buf: jax.Array, axis: str, s: int, t: int, cap: int):
    """all_to_all a [S*T, cap] owner-major buffer and return it tenant-major
    [T, S*cap] for the stack op (each row = one tenant's queries from every
    source shard)."""
    rx = _exchange(buf.reshape(s, t, cap), axis)          # [src S, T, cap]
    return rx.transpose(1, 0, 2).reshape(t, s * cap)


def _grid_return(resp: jax.Array, axis: str, s: int, t: int, cap: int):
    """Inverse of ``_grid_exchange`` for a [T, S*cap] response: back to the
    querying shards, owner-major [S*T, cap]."""
    tx = resp.reshape(t, s, cap).transpose(1, 0, 2)       # [src S, T, cap]
    return _exchange(tx, axis).reshape(s * t, cap)


def routed_stack_lookup(d: dhash.DHashState, keys: jax.Array,
                        tenant: jax.Array, axis: str,
                        owner_hfn: hashing.HashFn,
                        cap_factor: float = 2.0,
                        spill_slack: float | None = None):
    """Lookup a [Q] batch against the S×T grid.  ``d`` is THIS shard's
    T-table tenant stack; call inside shard_map.  Returns
    (found[Q], vals[Q], overflow[S·T]).

    Keys past ``cap = ceil(c·Q/(S·T))`` ride the spill slab — extra
    columns of the SAME buffer through the SAME one all_to_all pair — so
    with the default overflow-proof slab (``spill_slack=None``) every key
    is served even under 100% skew.  A slab column lives only in its
    owner's row ``shard·T + tenant``, so the exchange delivers it to the
    right shard with no extra machinery.  ``overflow`` stays the exact
    per-owner spill telemetry (slab pressure, feeds the cap controller);
    under a compact ``spill_slack`` the slab can run out, and only then do
    keys come back not-found (counted in ``Route.dropped``, never silently
    zeroed)."""
    s = lax.axis_size(axis)
    t = dhash.stack_size(d)
    q = keys.shape[0]
    cap = route_cap(cap_factor, q, s * t)
    spill_cap = route_spill_cap(q, cap, spill_slack)
    rt = _route(keys, grid_owner(keys, tenant, s, t, owner_hfn), s * t, cap,
                spill_cap)
    w = rt.send.shape[1]
    qk = _grid_exchange(rt.send, axis, s, t, w)
    qm = _grid_exchange(rt.smask, axis, s, t, w)
    f, v = dhash.stack_lookup(d, qk, qm)
    rf = _grid_return(f, axis, s, t, w)
    rv = _grid_return(v, axis, s, t, w)
    return (_unroute(rf, rt, fill=False).astype(bool),
            _unroute(rv, rt, fill=0), rt.overflow)


def routed_stack_update(d: dhash.DHashState, keys: jax.Array,
                        vals: jax.Array, mask: jax.Array, tenant: jax.Array,
                        axis: str, owner_hfn: hashing.HashFn,
                        op: Callable = dhash.stack_insert,
                        cap_factor: float = 2.0,
                        spill_slack: float | None = None):
    """Insert/delete a [Q] batch into the S×T grid (``op`` is
    ``dhash.stack_insert`` or ``dhash.stack_delete``).  Returns
    (d', ok[Q], overflow[S·T]).  Spilled keys ride the slab columns of the
    same buffer / same all_to_all pair (see ``routed_stack_lookup``): with
    the default overflow-proof slab every key is applied; under a compact
    ``spill_slack`` only slab-exhausted keys report ok=False.  Call inside
    shard_map."""
    s = lax.axis_size(axis)
    t = dhash.stack_size(d)
    q = keys.shape[0]
    cap = route_cap(cap_factor, q, s * t)
    spill_cap = route_spill_cap(q, cap, spill_slack)
    rt = _route(keys, grid_owner(keys, tenant, s, t, owner_hfn), s * t, cap,
                spill_cap)
    w = rt.send.shape[1]
    qk = _grid_exchange(rt.send, axis, s, t, w)
    qm = _grid_exchange(_route_payload(mask, rt) & rt.smask, axis, s, t, w)
    if op is dhash.stack_insert:
        qv = _grid_exchange(_route_payload(vals, rt), axis, s, t, w)
        d, ok = op(d, qk, qv, qm)
    else:
        d, ok = op(d, qk, qm)
    rok = _grid_return(ok, axis, s, t, w)
    return d, _unroute(rok, rt, fill=False).astype(bool), rt.overflow


def make_stacked(nshards: int, backend: str = "linear", capacity: int = 1024,
                 *, chunk: int = 256, seed: int = 0,
                 sharding: jax.sharding.Sharding | None = None,
                 **kw) -> dhash.DHashState:
    """Build ``nshards`` independent shard tables stacked on a leading axis
    (``dhash.make_stack`` — the same uniform-pytree stack the vmap ops
    batch; here the leading axis is sharded over the mesh instead).

    With ``sharding`` (one leading-axis index per device, e.g.
    ``NamedSharding(mesh, P(axis))``) each shard's table is built on the
    device that holds it, so no device ever holds more than its own shard;
    without it the whole stack is built on the default device.  Inside
    shard_map peel the leading axis with ``peel``.
    """
    if sharding is None:
        return dhash.make_stack(nshards, backend, capacity, chunk=chunk,
                                seed=seed, **kw)
    parts = []
    for dev, (idx, *_) in sharding.devices_indices_map((nshards,)).items():
        i = idx.start or 0
        if (idx.stop or nshards) - i != 1:
            raise ValueError(f"sharding must hold one shard per device, "
                             f"{dev} holds {idx}")
        # built in place on ``dev``: no second copy for the leading axis
        parts.append(jax.jit(
            lambda i=i: unpeel(dhash.make(backend, capacity, chunk=chunk,
                                          seed=seed + i, **kw)),
            out_shardings=jax.sharding.SingleDeviceSharding(dev))())
    return jax.tree_util.tree_map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (nshards,) + xs[0].shape[1:], sharding, list(xs)), *parts)


def peel(stacked):
    """Inside shard_map: view this shard's table (leading axis is size 1)."""
    return jax.tree_util.tree_map(lambda x: x[0], stacked)


def unpeel(d):
    """Inverse of peel for returning the updated shard."""
    return jax.tree_util.tree_map(lambda x: x[None], d)


def routed_service_step(d: dhash.DHashState, lookup_keys: jax.Array,
                        ins_keys: jax.Array, ins_vals: jax.Array,
                        del_keys: jax.Array, ins_mask: jax.Array,
                        del_mask: jax.Array, axis: str,
                        owner_hfn: hashing.HashFn):
    """The served step of a table hash-partitioned over ``axis``: this
    device's slice of a lookup, an insert and a delete batch, each routed
    exactly to its owners (the lookup in rounds, the insert and the delete
    at full width: see the module docstring), then one rebuild transition
    on this shard.  It holds no epoch swap: the engine swaps at its poll.
    Call inside shard_map.

    Returns ``(d', (found, vals, ok_i, ok_d), load, rounds)``: ``load``
    [S] counts the keys this device sent to each owner over the three
    batches, ``rounds`` the lookup's rounds (``routed_lookup``)."""
    found, vals, rl, rounds = routed_lookup(d, lookup_keys, axis, owner_hfn)
    # the insert writes the tables in place only after the lookup's loop
    # has read them: without the order the barrier gives, XLA copies each
    # table for the loop (tests/test_tpu_compile.py counts them)
    d, found, vals = lax.optimization_barrier((d, found, vals))
    d, ok_i, ri = routed_update(d, ins_keys, ins_vals, ins_mask, axis,
                                owner_hfn, op=dhash.insert)
    d, ok_d, rd = routed_update(d, del_keys, del_keys, del_mask, axis,
                                owner_hfn, op=dhash.delete)
    d = dhash.rebuild_step(d)
    return d, (found, vals, ok_i, ok_d), rl.hist + ri.hist + rd.hist, rounds
