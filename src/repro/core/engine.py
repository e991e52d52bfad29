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

from repro.core import backend as backends
from repro.core import dhash
from repro.core import policy as elastic
from repro.core.struct_utils import replace

I32 = jnp.int32

DEFAULT_POLL_EVERY = 32


def _put(lookup_keys, ins_keys, ins_vals, del_keys, ins_mask, del_mask):
    """A step's six operands on the device."""
    with span("dhash.engine.put"):
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


def _lookup(fn, state, keys):
    """A lookup batch through the jitted ``fn``, under the engine's spans."""
    with span("dhash.engine.lookup"):
        with span("dhash.engine.put"):
            k = jnp.asarray(keys, I32)
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
    """Drives a DHashState: user op batches + background rebuild progress."""

    state: dhash.DHashState
    continuous_rebuild: bool = False   # paper Fig 2: rebuild forever
    rebuild_seed: int = 1234
    poll_every: int = DEFAULT_POLL_EVERY   # host polls 1 of every K steps
    policy: elastic.ElasticPolicy | None = None   # elastic capacity decisions
    _stats: EngineStats = field(default_factory=EngineStats, repr=False)
    _step_fn: Callable | None = field(default=None, init=False, repr=False)
    _poll_fn: Callable | None = field(default=None, init=False, repr=False)
    _lookup_fn: Callable | None = field(default=None, init=False, repr=False)
    _count_fn: Callable | None = field(default=None, init=False, repr=False)
    _epoch0: int = field(default=0, init=False, repr=False)
    _last_poll_step: int = field(default=-1, init=False, repr=False)

    def __post_init__(self):
        if self.policy is not None and self.continuous_rebuild:
            raise ValueError("policy and continuous_rebuild are exclusive: "
                             "the policy decides when to rebuild")
        # take ownership: copy so donation never sees aliased or shared
        # buffers (e.g. a caller-held reference or zeros reused across leaves)
        self.state = jax.tree_util.tree_map(jnp.copy, self.state)
        if self.policy is not None:
            self.policy = jax.tree_util.tree_map(jnp.copy, self.policy)
            self._poll_fn = jax.jit(
                lambda d, p: (d.epoch, d.rebuilding, dhash.rebuild_done(d),
                              p.want_grow, p.want_shrink, p.target_capacity))
        else:
            self._poll_fn = jax.jit(
                lambda d: (d.epoch, d.rebuilding, dhash.rebuild_done(d)))
        # donate the state: tables update in place, no per-step copy (a jit
        # of this engine's own, so its cache counts this engine's retraces)
        self._step_fn = jax.jit(partial(_engine_step), donate_argnums=(0,))
        self._lookup_fn = jax.jit(dhash.lookup)
        self._count_fn = jax.jit(dhash.count_items)
        self._epoch0 = int(jax.device_get(self.state.epoch))

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
                                          del_keys, ins_mask, del_mask)
            with span("dhash.engine.dispatch"):
                if self.policy is not None:
                    self.state, self.policy, out = _policy_engine_step(
                        self.state, self.policy, lk, ik, iv, dk, im, dm,
                        swap_on_device=self._swap_on_device())
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
        plan (policy engines)."""
        if self.policy is not None:
            epoch, rebuilding, done, wg, ws, tgt = (
                int(x) for x in
                jax.device_get(self._poll_fn(self.state, self.policy)))
        else:
            epoch, rebuilding, done = (
                int(x) for x in jax.device_get(self._poll_fn(self.state)))
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
            epoch = int(jax.device_get(self.state.epoch))
            self._stats.host_syncs += 1
            self._last_poll_step = self._stats.steps
            self._stats.rebuilds_completed = epoch - self._epoch0
        return self._stats

    def request_rebuild(self, *, seed: int | None = None, new_table=None):
        """Begin a live rebuild (fails like the paper's trylock if one is
        already in progress)."""
        self._stats.host_syncs += 1
        if bool(jax.device_get(self.state.rebuilding)):
            return False  # -EBUSY
        if new_table is not None:
            new_table = jax.tree_util.tree_map(jnp.copy, new_table)  # own it
        self._start_rebuild(seed=seed, new_table=new_table)
        return True

    def _start_rebuild(self, *, seed: int | None = None, new_table=None):
        """``rebuild_start`` on a state the caller knows is not
        rebuilding (no device sync)."""
        self.state = dhash.rebuild_start(
            self.state, new_table,
            seed=self.rebuild_seed if seed is None else seed)
        self.rebuild_seed += 1

    def lookup(self, keys):
        return _lookup(self._lookup_fn, self.state, keys)

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
