"""The table every DHash service of the benchmark drives, built from the
seed: ``dhash.make`` (the op set is the program's default), filled on the
device in one jitted call through the program's own ``dhash.insert``, and
wrapped in ``DHashEngine`` with a live rehash started where the mix asks
for one (``"rehash": true``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

from bench import roofline
from bench.keys import KEY_MUL, VAL_MUL, VAL_SALT, salt_of

# keys per insert batch of the fill (fewer where the table holds fewer)
FILL_BATCH = 1 << 18


def device_keys(ids, salt):
    """``keys.Keys`` on the device: (keys, values) of uint32 ids."""
    import jax
    import jax.numpy as jnp

    def mix(x, mul, add):
        x = x * jnp.uint32(mul) + add
        return x ^ (x >> jnp.uint32(16))

    k = mix(ids, KEY_MUL, salt)
    v = mix(ids, VAL_MUL, salt ^ jnp.uint32(VAL_SALT))
    return (jax.lax.bitcast_convert_type(k, jnp.int32),
            jax.lax.bitcast_convert_type(v, jnp.int32))


def _populate(d, lo, salt, *, n: int, batch: int):
    """Insert ids ``[lo, lo + n)`` in batches of ``batch``; returns the
    state and the number of inserts acknowledged."""
    import jax
    import jax.numpy as jnp

    from repro.core import dhash

    def body(i, carry):
        d, acked = carry
        ids = lo + i.astype(jnp.uint32) * jnp.uint32(batch) + jnp.arange(
            batch, dtype=jnp.uint32)
        k, v = device_keys(ids, salt)
        d, ok = dhash.insert(d, k, v)
        return d, acked + ok.sum(dtype=jnp.int32)

    return jax.lax.fori_loop(0, n // batch, body, (d, jnp.int32(0)))


def populate(d, lo: int, n: int, seed: int):
    """Fill ``d`` with ids ``[lo, lo + n)``, in one jitted call from the
    seed; returns ``(state, inserts acknowledged)``."""
    import jax
    import jax.numpy as jnp
    batch = min(FILL_BATCH, n)
    if n % batch:
        raise ValueError(f"{n} keys do not split into batches of {batch}")
    fn = jax.jit(partial(_populate, n=n, batch=batch), donate_argnums=0)
    d, acked = fn(d, jnp.uint32(lo), jnp.uint32(salt_of(seed)))
    return d, int(acked)


def engine(cell, traffic, seed: int):
    """The cell's filled table in a ``DHashEngine``; returns ``(engine,
    facts)`` where ``facts`` names what was built (op set, shape, rehash,
    acknowledgements)."""
    from repro.core import dhash
    from repro.core.engine import DHashEngine

    cfg = cell.config
    rehash = bool(cell.traffic.get("rehash", False))
    d = dhash.make(cfg["backend"], capacity=cfg["keys"], chunk=cfg["chunk"],
                   seed=seed)
    lo, hi = traffic.populate_range()
    d, acked = populate(d, lo, hi - lo, seed)
    facts = {"op_set": "fused" if d.fused else "jnp",
             **dataclasses.asdict(roofline.table_shape(d)), "rehash": rehash,
             "populated": hi - lo, "populate_acked": acked}
    eng = DHashEngine(d, continuous_rebuild=rehash, rebuild_seed=seed + 7)
    del d
    if rehash:
        eng.request_rebuild()
    return eng, facts
