"""Engine: interleaves full-rate op batches with rebuild transitions.

This is the SPMD rendering of the paper's concurrency: "worker threads"
(batched lookup/insert/delete steps) run at full rate while a rebuild makes
incremental progress — one extract or land transition per engine step, with
the hazard window genuinely observable by the ops interleaved between the two
halves.

The steady state is **on-device**: the jitted step performs the op batch
and one rebuild transition, and holds nothing else.  With a ``fused``
DHashState the whole surface inside that step is kernel-backed — lookup,
insert, DELETE, the rebuild chunk extraction, and the hazard landing all
run through the Pallas probe/claim/extract kernels.  The rebuild-epoch
ordered lookup/delete are single-pass for ALL THREE fused backends (linear
probe2, its twochoice analogue, and the chain backend's arena-sorted
chain_probe2), and the two-level tile map keeps them single-pass even when
the rebuild target is a grown table — so a capacity-increasing rehash
sustains the same step rate as a same-size one (see docs/KERNELS.md).  A
fused chain state folds its arena maintenance into the same loop: inserts
and hazard landings re-sort the arena (cond-gated ``chain_maybe_compact``)
only when the dirty tail outgrows the dense window, and ``rebuild_start``
freezes the old arena fully sorted before each epoch's cursor scan.  State
buffers are **donated** (``donate_argnums``) so XLA updates tables in
place instead of copying them every step, and the host polls
``rebuild_done`` only every ``poll_every`` steps (default 32) — zero
``device_get`` round-trips on the other K-1 steps, so dispatch is never
serialized on a device->host sync.

**The epoch swap runs at the poll** (the paper's Alg. 3 lines 41-46): when
the poll reads ``rebuild_done``, ``dhash.epoch_swap`` swaps the two tables'
pytrees on the host, an O(1) pointer swap whatever their shapes, and in
continuous-rebuild mode ``rebuild_start`` opens the next epoch on a fresh
hash function (the first one at a poll before the first step).  Neither
adds a device read to the poll.  The swap is up to K-1 steps late, which
is safe because a completed-but-unswapped rebuild still answers every op
correctly through the ordered check; those steps run no migration.  A swap inside
the step would cost whole-table passes on every step whether or not an
epoch ends (``finish_same_shape`` selects every table array, and a
``lax.cond`` around it makes XLA copy the tables into its branches).

Ownership note: the engine donates its state buffers to the jitted step, so
after the first ``step()`` the ``DHashState`` passed to the constructor must
not be used elsewhere.

Used by the benchmarks (continuous-rebuild mode reproduces the paper's Fig 2
setup) and by the serving engine for live cache rehash.

Observability: each ``step`` is a host span ``dhash.engine.step`` (its
step number as the span argument ``step``) holding ``dhash.engine.put``
(the operands to the device), ``dhash.engine.dispatch`` (the jitted call)
and, once every ``poll_every`` steps, ``dhash.engine.poll``; each
``lookup`` is ``dhash.engine.lookup`` holding ``put`` and ``dispatch``.
A poll that swaps the epochs holds ``dhash.engine.swap``.
They are ``jax.profiler.TraceAnnotation``s, so they land in a profiler
trace on the device ops' clock and cost about a microsecond each
when no profiler runs; nothing else records them.

**A table hash-partitioned over devices** is the same engine on a stack
from ``distributed.make_stacked(S, ..., sharding=NamedSharding(mesh,
P(axis)))``: one table per device.  The engine reads the mesh from the
state's sharding and takes the fixed ``owner`` hash.  Its step
(``routed_step_fn``) is one ``shard_map`` program:
``distributed.routed_service_step`` routes each device's contiguous slice
of the lookup, insert and delete batches to their owners at full width
and back, then runs one rebuild transition on every shard.  ``put``
places each operand one slice per device, straight from the host.  The
poll reads every shard's epoch, rebuild flag and ``rebuild_done`` (they
run the same transitions, so they agree, or the poll raises) and the last
step's per-owner loads, in its one ``device_get``; the swap is the same
pytree exchange, and the next epoch opens on every shard in its own
standby table (``routed_start_fn``).  ``stats.routed_lanes`` and
``stats.routed_peak_load`` count the lanes a shard probes per step and
the busiest owner's keys.  A single table compiles to the same step as
ever.

``DHashStackEngine`` is the multi-table variant: it drives a
``dhash.make_stack`` state — T independent tables vmapped inside one jitted
step, each with its OWN rebuild epoch (staggered live rehashes across
tenants) — through the same donation + K-step polling treatment.  Its
per-table swaps cannot be pointer swaps, so its step keeps
``finish_same_shape`` and ``rebuild_autostart``; so does the policy engine's
step for same-shape tables.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import backend as backends
from repro.core import dhash, distributed, hashing
from repro.core import policy as elastic
from repro.core.struct_utils import replace

I32 = jnp.int32

DEFAULT_POLL_EVERY = 32


def _put(lookup_keys, ins_keys, ins_vals, del_keys, ins_mask, del_mask,
         sharding: NamedSharding | None = None):
    """A step's six operands on the device, or with ``sharding`` one
    contiguous slice of each per device along its axis (straight from the
    host: nothing goes to one device first)."""
    with span("dhash.engine.put"):
        if sharding is not None:
            def ones(x):
                return np.ones(np.shape(x), bool)
            return tuple(jax.device_put(np.asarray(x, t), sharding)
                         for x, t in ((lookup_keys, np.int32),
                                      (ins_keys, np.int32),
                                      (ins_vals, np.int32),
                                      (del_keys, np.int32),
                                      (ones(ins_keys) if ins_mask is None
                                       else ins_mask, bool),
                                      (ones(del_keys) if del_mask is None
                                       else del_mask, bool)))
        lk = jnp.asarray(lookup_keys, I32)
        ik = jnp.asarray(ins_keys, I32)
        iv = jnp.asarray(ins_vals, I32)
        dk = jnp.asarray(del_keys, I32)
        im = (jnp.ones(ik.shape, bool) if ins_mask is None
              else jnp.asarray(ins_mask))
        dm = (jnp.ones(dk.shape, bool) if del_mask is None
              else jnp.asarray(del_mask))
    return lk, ik, iv, dk, im, dm


def _count_and_poll(eng, ops: int) -> None:
    """Count a step, and poll the device once every ``poll_every`` steps."""
    eng._stats.steps += 1
    eng._stats.ops += ops
    if eng.poll_every <= 1 or eng._stats.steps % eng.poll_every == 0:
        with span("dhash.engine.poll"):
            eng._poll()


def _lookup(fn, state, keys, sharding: NamedSharding | None = None):
    """A lookup batch through the jitted ``fn``, under the engine's spans."""
    with span("dhash.engine.lookup"):
        with span("dhash.engine.put"):
            k = (jnp.asarray(keys, I32) if sharding is None
                 else jax.device_put(np.asarray(keys, np.int32), sharding))
        with span("dhash.engine.dispatch"):
            return fn(state, k)


@dataclass
class EngineStats:
    steps: int = 0
    ops: int = 0
    rebuilds_completed: int = 0
    host_syncs: int = 0         # engine-internal device_get round-trips
    grows: int = 0              # policy-applied capacity increases
    shrinks: int = 0            # policy-applied capacity decreases
    routed_lanes: int = 0       # sharded: lanes each shard probes per step
                                # (static: S x the per-device batch widths)
    routed_peak_load: int = 0   # sharded: keys the busiest owner served in
                                # the last step before the last poll


def _agreed(x) -> int:
    """A scalar the device read, or the one value every shard of a stack
    read; shards that disagree are out of step, which is an error."""
    x = np.asarray(x)
    if (x != x.flat[0]).any():
        raise RuntimeError(f"the shards are out of step: {x.tolist()}")
    return int(x.flat[0])


def _sharding_of(state: dhash.DHashState) -> NamedSharding | None:
    """None for one table; for a stack laid one table per device along one
    mesh axis (``distributed.make_stacked(sharding=...)``), its sharding."""
    if state.cursor.ndim == 0:
        return None
    sh, n = state.cursor.sharding, state.cursor.shape[0]
    if isinstance(sh, NamedSharding):
        axis = sh.spec[0] if len(sh.spec) == 1 else None
        if sh.mesh.size == 1 == n and len(sh.mesh.axis_names) == 1:
            axis = sh.mesh.axis_names[0]    # one device: no axis to name
        if isinstance(axis, str) and sh.mesh.shape[axis] == n:
            return NamedSharding(sh.mesh, P(axis))
    raise ValueError("DHashEngine drives one table, or a stack sharded one "
                     "table per device along one mesh axis; got a "
                     f"[{n}] stack laid out as {sh}")


def _smap(f, sharding: NamedSharding, n_in: int, out_specs,
          replicated_in: int = 0):
    """``f`` under ``shard_map`` over ``sharding``'s mesh: ``n_in``
    arguments split along its axis, then ``replicated_in`` whole ones."""
    spec = P(sharding.spec[0])
    return jax.shard_map(f, mesh=sharding.mesh,
                         in_specs=(spec,) * n_in + (P(),) * replicated_in,
                         out_specs=out_specs, check_vma=False)


def routed_step_fn(sharding: NamedSharding, owner: hashing.HashFn):
    """The jitted engine step of a stack sharded along ``sharding``'s axis:
    ``distributed.routed_service_step`` on every shard, the state donated.
    Returns ``(state', (found, vals, ok_i, ok_d), load)``, ``load`` [S, S]
    the keys each device sent to each owner."""
    axis = sharding.spec[0]
    spec = P(axis)

    def body(st, lk, ik, iv, dk, im, dm):
        d, out, load = distributed.routed_service_step(
            distributed.peel(st), lk, ik, iv, dk, im, dm, axis, owner)
        # the barrier keeps XLA from sinking the unpeel's reshape into the
        # rebuild's extract conditional, where it copies a table array
        # into each branch (tests/test_tpu_compile.py counts them)
        d = jax.lax.optimization_barrier(d)
        return distributed.unpeel(d), out, load[None]

    return jax.jit(_smap(body, sharding, 7, (spec, (spec,) * 4, spec)),
                   donate_argnums=(0,))


def routed_start_fn(sharding: NamedSharding):
    """The jitted rebuild start of a stack sharded along ``sharding``'s
    axis: ``dhash.rebuild_reopen`` on every shard, on its own device, into
    the shard's own standby table (donated: no table is allocated)."""
    spec = P(sharding.spec[0])

    def body(st, salt):
        return distributed.unpeel(dhash.rebuild_reopen(distributed.peel(st),
                                                       salt))

    # keep_unused: the cleared standby's buffers are never read, and only
    # as parameters can they take its zeros in place
    return jax.jit(_smap(body, sharding, 1, spec, replicated_in=1),
                   donate_argnums=(0,), keep_unused=True)


@partial(jax.jit, static_argnames=("swap_on_device",), donate_argnums=(0, 1))
def _policy_engine_step(d, pol, lk, ik, iv, dk, imask, dmask, *,
                        swap_on_device: bool):
    """The policy-driven engine step (module level so every engine instance
    shares ONE jit cache — a resize retrace warms the cache for all engines
    with the same geometry, e.g. a bench's warmup and timed engines).

    Identical op sequence to the plain step, with the lookup routed through
    ``lookup_counted`` (probe telemetry is a kernel output, not an extra
    pass) and one ``policy_step`` evaluation appended.  While old/new are
    shape-mismatched mid-resize (``swap_on_device=False``) the policy is
    plan-only: no on-device autostart against the wrong geometry."""
    d, (found, vals) = dhash.lookup_counted(d, lk, probe_hi=pol.probe_hi)
    d, ok_i = dhash.insert(d, ik, iv, imask)
    d, ok_d = dhash.delete(d, dk, dmask)
    d = dhash.rebuild_step(d)
    if swap_on_device:
        d = dhash.finish_same_shape(d)
    pol, d = elastic.policy_step(pol, d, allow_autostart=swap_on_device)
    return d, pol, (found, vals, ok_i, ok_d)


def _engine_step(d, lk, ik, iv, dk, imask, dmask):
    """The plain engine step: the op batch and one rebuild transition.  It
    holds no epoch swap and no rebuild start: both run at the host's poll
    (``DHashEngine._poll``)."""
    found, vals = dhash.lookup(d, lk)
    d, ok_i = dhash.insert(d, ik, iv, imask)
    d, ok_d = dhash.delete(d, dk, dmask)
    d = dhash.rebuild_step(d)
    return d, (found, vals, ok_i, ok_d)


@dataclass
class DHashEngine:
    """Drives a DHashState: user op batches + background rebuild progress.
    The state is one table, or a stack sharded one table per device (the
    module docstring), whose keys ``owner`` partitions."""

    state: dhash.DHashState
    continuous_rebuild: bool = False   # paper Fig 2: rebuild forever
    rebuild_seed: int = 1234
    poll_every: int = DEFAULT_POLL_EVERY   # host polls 1 of every K steps
    policy: elastic.ElasticPolicy | None = None   # elastic capacity decisions
    owner: hashing.HashFn | None = None   # a sharded stack's owner hash
    _stats: EngineStats = field(default_factory=EngineStats, repr=False)
    _step_fn: Callable | None = field(default=None, init=False, repr=False)
    _poll_fn: Callable | None = field(default=None, init=False, repr=False)
    _lookup_fn: Callable | None = field(default=None, init=False, repr=False)
    _count_fn: Callable | None = field(default=None, init=False, repr=False)
    _start_fn: Callable | None = field(default=None, init=False, repr=False)
    _sharding: NamedSharding | None = field(default=None, init=False,
                                            repr=False)
    _load: jax.Array | None = field(default=None, init=False, repr=False)
    _epoch0: int = field(default=0, init=False, repr=False)
    _last_poll_step: int = field(default=-1, init=False, repr=False)

    def __post_init__(self):
        if self.policy is not None and self.continuous_rebuild:
            raise ValueError("policy and continuous_rebuild are exclusive: "
                             "the policy decides when to rebuild")
        self._sharding = sh = _sharding_of(self.state)
        if sh is not None and (self.policy is not None or self.owner is None):
            raise ValueError("a sharded stack takes the owner hash that "
                             "partitions its keys, and no policy")
        # take ownership: copy so donation never sees aliased or shared
        # buffers (e.g. a caller-held reference or zeros reused across leaves)
        self.state = jax.tree_util.tree_map(jnp.copy, self.state)
        if self.policy is not None:
            self.policy = jax.tree_util.tree_map(jnp.copy, self.policy)
            self._poll_fn = jax.jit(
                lambda d, p: (d.epoch, d.rebuilding, dhash.rebuild_done(d),
                              p.want_grow, p.want_shrink, p.target_capacity))
        else:
            done = (dhash.rebuild_done if sh is None
                    else dhash.stack_rebuild_done)
            self._poll_fn = jax.jit(
                lambda d: (d.epoch, d.rebuilding, done(d)))
        if sh is None:
            # donate the state: tables update in place, no per-step copy (a
            # jit of this engine's own, so its cache counts its retraces)
            self._step_fn = jax.jit(partial(_engine_step),
                                    donate_argnums=(0,))
            self._lookup_fn = jax.jit(dhash.lookup)
            self._count_fn = jax.jit(dhash.count_items)
        else:
            axis, owner = sh.spec[0], self.owner
            self._step_fn = routed_step_fn(sh, owner)
            self._start_fn = routed_start_fn(sh)
            self._lookup_fn = jax.jit(_smap(
                lambda st, k: distributed.routed_lookup(
                    distributed.peel(st), k, axis, owner)[:2],
                sh, 2, (P(axis), P(axis))))
            self._count_fn = jax.jit(
                lambda d: dhash.stack_count_items(d).sum())
        self._epoch0 = _agreed(jax.device_get(self.state.epoch))

    # -- jitted step ---------------------------------------------------------

    def _swap_on_device(self) -> bool:
        """True iff old/new share static shapes, so the policy step can run
        the epoch swap on the device (host metadata only — no device
        sync)."""
        old, new = self.state.old, self.state.new
        if (jax.tree_util.tree_structure(old)
                != jax.tree_util.tree_structure(new)):
            return False
        return all(
            getattr(a, "shape", None) == getattr(b, "shape", None)
            and getattr(a, "dtype", None) == getattr(b, "dtype", None)
            for a, b in zip(jax.tree_util.tree_leaves(old),
                            jax.tree_util.tree_leaves(new)))

    def step(self, lookup_keys, ins_keys, ins_vals, del_keys,
             ins_mask=None, del_mask=None):
        with span("dhash.engine.step", step=self._stats.steps):
            if self.continuous_rebuild and self._stats.steps == 0:
                # the first epoch opens here, not in the constructor: the
                # caller still holds the state there, so a fresh table
                # would raise the peak memory by one table
                with span("dhash.engine.poll"):
                    self._poll()
            lk, ik, iv, dk, im, dm = _put(lookup_keys, ins_keys, ins_vals,
                                          del_keys, ins_mask, del_mask,
                                          self._sharding)
            with span("dhash.engine.dispatch"):
                if self.policy is not None:
                    self.state, self.policy, out = _policy_engine_step(
                        self.state, self.policy, lk, ik, iv, dk, im, dm,
                        swap_on_device=self._swap_on_device())
                elif self._sharding is not None:
                    self.state, out, self._load = self._step_fn(
                        self.state, lk, ik, iv, dk, im, dm)
                    # every owner row is as wide as the whole batch
                    self._stats.routed_lanes = lk.size + ik.size + dk.size
                else:
                    self.state, out = self._step_fn(self.state, lk, ik, iv,
                                                    dk, im, dm)
            _count_and_poll(self, lk.size + ik.size + dk.size)
            return out

    # -- host-side polling (1 of every K steps) ------------------------------

    def _poll(self):
        """One batched device_get: refresh stats; swap the epochs of a
        completed rebuild (span ``dhash.engine.swap``); start the next
        rebuild in continuous mode; apply the policy's published resize
        plan (policy engines).  A sharded stack's shards run the same
        transitions, so they must read the same epoch, rebuild flag and
        ``rebuild_done``; the poll raises rather than swap shards out of
        step."""
        if self.policy is not None:
            epoch, rebuilding, done, wg, ws, tgt = (
                int(x) for x in
                jax.device_get(self._poll_fn(self.state, self.policy)))
        else:
            polled, load = jax.device_get((self._poll_fn(self.state),
                                           self._load))
            epoch, rebuilding, done = (_agreed(x) for x in polled)
            if load is not None:
                self._stats.routed_peak_load = int(load.sum(axis=0).max())
            wg = ws = 0
        self._stats.host_syncs += 1
        self._last_poll_step = self._stats.steps
        if done:
            # the epoch swap: an O(1) pointer swap of the two tables (a
            # policy step swaps same-shape tables on the device itself, so
            # there this is a shape-changing rebuild)
            with span("dhash.engine.swap"):
                self.state = dhash.epoch_swap(self.state)
            epoch += 1
            rebuilding = False
            # the published plan predates the swap we just applied — drop
            # it; the device policy re-evaluates against the new geometry
            # before the next poll can act
            wg = ws = 0
            if self.policy is not None:
                # a finished shape-changing resize leaves the dead table as
                # the standby; restore a same-shape standby so the epoch
                # swap (and tombstone-reclaim autostarts) return on-device
                be = backends.get(self.state.backend)
                self.state = replace(
                    self.state, new=be.fresh_like(self.state.old,
                                                  self.rebuild_seed))
                self.rebuild_seed += 1
        self._stats.rebuilds_completed = epoch - self._epoch0
        if self.continuous_rebuild and not rebuilding:
            self._start_rebuild()
        if self.policy is not None and not rebuilding and (wg or ws):
            self._apply_resize(grow=bool(wg), target_entries=tgt)

    def _apply_resize(self, *, grow: bool, target_entries: int):
        """Materialize the policy's published plan: size the new table,
        adapt the tile-map residency to the slot ratio, and begin the live
        migration.  Skips plans that round to the CURRENT slot count (the
        power-of-two sizing makes repeated wants at a capacity floor free) —
        except a probe-triggered grow, which is force-bumped to the next
        size up: clustering wants more slots even when the load does not."""
        be = backends.get(self.state.backend)
        cur_slots = int(be.capacity_of(self.state.old))
        tgt = int(target_entries)
        new_slots = elastic.resolve_slots(be, tgt)
        if grow and new_slots <= cur_slots:
            tgt = int(cur_slots * 0.75) + 1
            new_slots = elastic.resolve_slots(be, tgt)
        if new_slots == cur_slots or (not grow and new_slots > cur_slots):
            return
        nres = elastic.adapt_nres_cap(self.policy, cur_slots, new_slots,
                                      base=be.nres_cap)
        new_table = be.make(tgt, self.rebuild_seed)
        if not self.request_rebuild(new_table=new_table):
            return   # lost the trylock (a reclaim rehash is mid-flight)
        # the resize consumes the plan and the probe sample window
        self.state = replace(self.state, nres_cap=nres,
                             lookups=jnp.asarray(0, I32),
                             expensive=jnp.asarray(0, I32))
        self.policy = replace(self.policy,
                              want_grow=jnp.asarray(False),
                              want_shrink=jnp.asarray(False))
        if grow:
            self._stats.grows += 1
        else:
            self._stats.shrinks += 1

    @property
    def stats(self) -> EngineStats:
        """Engine statistics.  Reading them performs a refresh-only device
        read if the engine stepped since the last poll (so
        ``rebuilds_completed`` is current) — it never finishes or starts a
        rebuild (those happen only on ``step()``'s K-step poll), and
        steady-state ``step()`` calls themselves stay sync-free."""
        if self._stats.steps != self._last_poll_step:
            epoch = _agreed(jax.device_get(self.state.epoch))
            self._stats.host_syncs += 1
            self._last_poll_step = self._stats.steps
            self._stats.rebuilds_completed = epoch - self._epoch0
        return self._stats

    def request_rebuild(self, *, seed: int | None = None, new_table=None):
        """Begin a live rebuild (fails like the paper's trylock if one is
        already in progress)."""
        self._stats.host_syncs += 1
        if _agreed(jax.device_get(self.state.rebuilding)):
            return False  # -EBUSY
        if new_table is not None:
            new_table = jax.tree_util.tree_map(jnp.copy, new_table)  # own it
        self._start_rebuild(seed=seed, new_table=new_table)
        return True

    def _start_rebuild(self, *, seed: int | None = None, new_table=None):
        """``rebuild_start`` on a state the caller knows is not
        rebuilding (no device sync).  A sharded stack opens the epoch on
        every shard into its own standby table (``routed_start_fn``)."""
        seed = self.rebuild_seed if seed is None else seed
        if self._sharding is None:
            self.state = dhash.rebuild_start(self.state, new_table,
                                             seed=seed)
        elif new_table is not None:
            raise ValueError("a sharded stack rebuilds into its standby "
                             "tables, not into a table from the caller")
        else:
            self.state = self._start_fn(self.state,
                                        np.int32(seed % (1 << 31)))
        self.rebuild_seed += 1

    def lookup(self, keys):
        return _lookup(self._lookup_fn, self.state, keys, self._sharding)

    def count(self) -> int:
        self._stats.host_syncs += 1
        return int(jax.device_get(self._count_fn(self.state)))

    def _step_cache_size(self) -> int:
        """Jit cache entries of the step (retrace detector)."""
        return self._step_fn._cache_size()


@dataclass
class DHashStackEngine:
    """Drives a ``dhash.make_stack`` state: T independent tables batched by
    ``jax.vmap`` inside ONE jitted step (multi-tenant serving loop).

    Per step, every table runs its op batch ([T, Q] operands), one rebuild
    transition, and its own on-device epoch swap — epochs are fully
    INDEPENDENT across the stack: ``request_rebuild(mask)`` starts rebuilds
    on any subset of tables (device-side ``rebuild_autostart`` under the
    mask, so a stack engine never needs the host-level ``rebuild_start``),
    and in ``continuous_rebuild`` mode every table that finishes an epoch
    immediately opens the next.  The same donation + K-step polling
    treatment as ``DHashEngine`` applies; stacks only support same-shape
    rebuilds (the vmapped swap is ``finish_same_shape``)."""

    state: dhash.DHashState                # stacked: every leaf leads with [T]
    continuous_rebuild: bool = False
    poll_every: int = DEFAULT_POLL_EVERY
    policy: elastic.ElasticPolicy | None = None   # in-place mode; [T]-stacked
    _stats: EngineStats = field(default_factory=EngineStats, repr=False)
    _step_fn: Callable | None = field(default=None, init=False, repr=False)
    _start_fn: Callable | None = field(default=None, init=False, repr=False)
    _lookup_fn: Callable | None = field(default=None, init=False, repr=False)
    _count_fn: Callable | None = field(default=None, init=False, repr=False)
    _epoch0: jnp.ndarray | None = field(default=None, init=False, repr=False)
    _last_poll_step: int = field(default=-1, init=False, repr=False)

    def __post_init__(self):
        self.state = jax.tree_util.tree_map(jnp.copy, self.state)
        self.n_tables = dhash.stack_size(self.state)
        autostart = self.continuous_rebuild
        if self.policy is not None:
            if self.continuous_rebuild:
                raise ValueError("policy and continuous_rebuild are "
                                 "exclusive: the policy decides when to "
                                 "rebuild")
            if not self.policy.in_place:
                raise ValueError("stack engines need an in_place policy: "
                                 "vmapped tables cannot change static shape")
            # accept a single (unstacked) policy and broadcast it
            if self.policy.armed.ndim == 0:
                self.policy = elastic.stack(self.policy, self.n_tables)
            self.policy = jax.tree_util.tree_map(jnp.copy, self.policy)
        probe_hi = None if self.policy is None else self.policy.probe_hi

        def fused(d, lk, ik, iv, dk, imask, dmask):
            found, vals = dhash.stack_lookup(d, lk)
            d, ok_i = dhash.stack_insert(d, ik, iv, imask)
            d, ok_d = dhash.stack_delete(d, dk, dmask)
            d = dhash.stack_rebuild_step(d)
            d = dhash.stack_finish_same_shape(d)
            if autostart:
                d = dhash.stack_autostart(d)
            return d, (found, vals, ok_i, ok_d)

        def fused_policy(d, pol, lk, ik, iv, dk, imask, dmask):
            d, (found, vals) = jax.vmap(
                lambda dd, kk: dhash.lookup_counted(dd, kk,
                                                    probe_hi=probe_hi))(d, lk)
            d, ok_i = dhash.stack_insert(d, ik, iv, imask)
            d, ok_d = dhash.stack_delete(d, dk, dmask)
            d = dhash.stack_rebuild_step(d)
            d = dhash.stack_finish_same_shape(d)
            # per-table triggers: each tenant fires its own same-shape
            # rehash independently (in-place mode), latched by its own
            # armed hysteresis
            pol, d = elastic.stack_policy_step(pol, d)
            return d, pol, (found, vals, ok_i, ok_d)

        if self.policy is not None:
            self._step_fn = jax.jit(fused_policy, donate_argnums=(0, 1))
        else:
            self._step_fn = jax.jit(fused, donate_argnums=(0,))
        self._start_fn = jax.jit(dhash.stack_autostart)
        self._lookup_fn = jax.jit(dhash.stack_lookup)
        self._count_fn = jax.jit(dhash.stack_count_items)
        self._epoch0 = np.asarray(jax.device_get(self.state.epoch))

    def step(self, lookup_keys, ins_keys, ins_vals, del_keys,
             ins_mask=None, del_mask=None):
        """One batched step for all T tables: operands are [T, Q]."""
        with span("dhash.engine.step", step=self._stats.steps):
            lk, ik, iv, dk, im, dm = _put(lookup_keys, ins_keys, ins_vals,
                                          del_keys, ins_mask, del_mask)
            with span("dhash.engine.dispatch"):
                if self.policy is not None:
                    self.state, self.policy, out = self._step_fn(
                        self.state, self.policy, lk, ik, iv, dk, im, dm)
                else:
                    self.state, out = self._step_fn(self.state, lk, ik, iv,
                                                    dk, im, dm)
            _count_and_poll(self, lk.size + ik.size + dk.size)
            return out

    def _poll(self):
        epochs = np.asarray(jax.device_get(self.state.epoch))
        self._stats.host_syncs += 1
        self._last_poll_step = self._stats.steps
        self._stats.rebuilds_completed = int((epochs - self._epoch0).sum())

    @property
    def stats(self) -> EngineStats:
        """Reading stats performs a refresh-only device read ONLY when the
        engine stepped since the last poll (same contract as
        ``DHashEngine.stats`` — repeated reads in a step loop stay
        sync-free)."""
        if self._stats.steps != self._last_poll_step:
            self._poll()
        return self._stats

    def request_rebuild(self, mask=None) -> None:
        """Start a rebuild on the selected tables ([T] bool; all by default).
        Tables mid-rebuild are untouched (the paper's trylock: the request
        is simply lost for them)."""
        m = (jnp.ones((self.n_tables,), bool) if mask is None
             else jnp.asarray(mask, bool))
        self.state = self._start_fn(self.state, m)

    def lookup(self, keys):
        return _lookup(self._lookup_fn, self.state, keys)

    def counts(self) -> np.ndarray:
        """[T] live-entry counts (one host sync)."""
        self._stats.host_syncs += 1
        return np.asarray(jax.device_get(self._count_fn(self.state)))
