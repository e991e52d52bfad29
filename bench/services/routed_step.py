"""Each batch through ``DHashEngine.step`` on a table hash-partitioned over
the cell's chips.

``distributed.make_stacked`` builds one shard per chip, on its chip.  The
fill makes each chip's slice of every fill batch on that chip and routes
it to the owner shards through the program's routed insert, in one jitted
call.  It routes at a tight cap with a compact spill slab, as set-up may:
a key the slab cannot carry comes back refused and shows in
``populate_acked``.  The engine then routes every batch's lookups,
inserts and deletes exactly (``distributed.routed_service_step``) and
runs one rebuild transition on every shard.  The owner hash is fixed, as
a sharded store's key -> hash slot -> node map is.  ``facts`` give one
shard's shape.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

from bench import cells, roofline, table
from bench.keys import salt_of

step = cells.module("services", "engine_step")

AXIS = "shard"
OWNER_SEED = 0x5EED0A4E      # the fixed owner hash: mix32 from this seed
FILL_BATCH = 1 << 13         # keys per chip per fill batch: the routed
                             # insert sorts its lanes, and the TPU compiler
                             # takes seconds for a sort this wide but a
                             # minute or more for one of 2^15 lanes
FILL_CAP_FACTOR = 1.25       # fill route: 1.25 x a shard's share per row
FILL_SLACK = 1 / 64          # and a spill slab of 1/64 of the batch


def owner_hash():
    from repro.core import hashing
    return hashing.fresh("mix32", OWNER_SEED)


def _populate(st, lo, salt, *, n: int, batch: int, owner):
    """Inside shard_map: insert ids ``[lo, lo + n)`` over all shards, in
    global batches of ``S * batch`` ids of which this device makes and
    routes its contiguous slice; returns the stack and this device's
    inserts acknowledged."""
    import jax
    import jax.numpy as jnp

    from repro.core import dhash, distributed

    s = jax.lax.axis_size(AXIS)
    me = jax.lax.axis_index(AXIS).astype(jnp.uint32)
    cap = distributed.route_cap(FILL_CAP_FACTOR, batch, s)
    on = jnp.ones((batch,), bool)

    def body(i, carry):
        d, acked = carry
        first = lo + (i.astype(jnp.uint32) * s + me) * jnp.uint32(batch)
        k, v = table.device_keys(first + jnp.arange(batch, dtype=jnp.uint32),
                                 salt)
        d, ok, _ = distributed.routed_update(
            d, k, v, on, AXIS, owner, op=dhash.insert, cap=cap,
            spill_slack=FILL_SLACK)
        return d, acked + ok.sum(dtype=jnp.int32)

    d, acked = jax.lax.fori_loop(0, n // (s * batch), body,
                                 (distributed.peel(st), jnp.int32(0)))
    return distributed.unpeel(d), acked[None]


def populate(st, sharding, lo: int, n: int, seed: int, owner):
    """Fill the sharded stack with ids ``[lo, lo + n)`` in one jitted
    call; returns ``(stack, inserts acknowledged over all shards)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    shards = sharding.mesh.shape[AXIS]
    batch = min(FILL_BATCH, n // shards)
    if n % (shards * batch):
        raise ValueError(f"{n} keys do not split into {shards} x {batch}")
    fn = jax.jit(jax.shard_map(
        partial(_populate, n=n, batch=batch, owner=owner),
        mesh=sharding.mesh, in_specs=(P(AXIS), P(), P()),
        out_specs=(P(AXIS), P(AXIS)), check_vma=False), donate_argnums=0)
    st, acked = fn(st, jnp.uint32(lo), jnp.uint32(salt_of(seed)))
    return st, int(np.asarray(jax.device_get(acked)).sum())


class OneShard:
    """The engine as the benchmark's control and fault wrappers reach it
    (``bench/control.py``, ``bench/tests/``): their ``engine.state`` is one
    table.  On one chip that is the stack's one shard, which owns every
    key, and setting it puts the table back as that shard.  A stack of
    more shards answers only through routed lookups, so a control of it
    routes its own (``bench/tests/test_bench_routed.py``)."""

    def __init__(self, engine):
        self.sharded = engine

    def _stack(self):
        st = self.sharded.state
        if st.cursor.shape[0] != 1:
            raise NotImplementedError(
                f"{st.cursor.shape[0]} shards answer through routed "
                "lookups; this view holds one table")
        return st

    @property
    def state(self):
        from repro.core import distributed
        return distributed.peel(self._stack())

    @state.setter
    def state(self, d):
        import jax

        from repro.core import distributed
        sharding = self._stack().cursor.sharding
        self.sharded.state = jax.device_put(distributed.unpeel(d), sharding)


class RoutedStep(step.EngineStep):
    """``EngineStep`` on the sharded engine (``self.sharded``)."""

    def __init__(self, engine):
        self.sharded = engine
        self.engine = OneShard(engine)

    def submit(self, b):
        return self.sharded.step(b.look, b.ins, b.ins_vals, b.dels)

    def live(self) -> int:
        return self.sharded.count()


def build(cell, traffic, seed: int):
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import distributed
    from repro.core.engine import DHashEngine

    cfg = cell.config
    rehash = bool(cell.traffic.get("rehash", False))
    sharding = NamedSharding(
        Mesh(np.asarray(jax.devices()[: cell.chips]), (AXIS,)), P(AXIS))
    owner = owner_hash()
    d = distributed.make_stacked(cell.chips, cfg["backend"],
                                 capacity=cfg["keys"], chunk=cfg["chunk"],
                                 seed=seed, sharding=sharding)
    lo, hi = traffic.populate_range()
    d, acked = populate(d, sharding, lo, hi - lo, seed, owner)
    facts = {"op_set": "fused" if d.fused else "jnp",
             **dataclasses.asdict(roofline.table_shape(d)), "rehash": rehash,
             "shards": cell.chips, "populated": hi - lo,
             "populate_acked": acked}
    eng = DHashEngine(d, continuous_rebuild=rehash, rebuild_seed=seed + 7,
                      owner=owner)
    del d
    if rehash:
        eng.request_rebuild()
    return RoutedStep(eng), facts
