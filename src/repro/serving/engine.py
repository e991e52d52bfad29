"""Serving engine: continuous batching over a DHash-paged KV cache.

Host-side driver (the framework's serve driver, deliverable (b)):
* fixed-slot continuous batching: finished sequences free their pages and
  the slot is re-admitted from the queue on the same step boundary;
* prefix-cache admission: longest cached block-prefix is reused;
* **live rehash**: when the page table's load factor or probe-length stats
  degrade (bursty admission / adversarial patterns), the engine starts a
  DHash rebuild; every decode step advances it one transition — serving
  latency is flat through the entire rehash (measured in
  benchmarks/bench_kvcache.py);
* **multi-tenant page tables** (``ServeConfig.n_tenants > 1``): the page
  table is a per-tenant ``dhash.make_stack`` (tenant = seq_id % n_tenants);
  decode resolves every tenant in one vmapped stack op and rehash epochs
  run independently per tenant — only the tenants whose load degraded pay a
  rebuild, with on-device epoch swaps.

The jitted step is fully paged: per layer, K/V of the new token are written
to the page pool and attention runs flash-decoding over DHash-resolved pages.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import dhash
from repro.core import policy as elastic
from repro.models import transformer
from repro.models.attention import project_qkv
from repro.models.layers import apply_rope, rms_norm, swiglu
from repro.serving import kvcache, prefix_cache
from repro.serving.kvcache import PagedKV

F32 = jnp.float32
I32 = jnp.int32


@dataclass(frozen=True)
class ServeConfig:
    max_seqs: int = 8
    page_size: int = 16
    n_pages: int = 512
    max_blocks: int = 64          # per-seq block bound (= max_len / page_size)
    max_new_tokens: int = 32
    rehash_load_factor: float = 0.7
    n_tenants: int = 1            # > 1: per-tenant page-table stack
                                  # (tenant = seq_id % n_tenants) with
                                  # INDEPENDENT live rehash epochs
    cap_factor: float = 2.0       # tenant-router cap c: send buffers are
                                  # [T, ceil(c*N/T) + spill_cap] (<= 0:
                                  # full width); overflow under skew rides
                                  # the spill slab in the same single pass
    spill_slack: float = 1.0      # spill-slab budget (kvcache.make):
                                  # 1.0 = overflow-proof; < 1 = compact
                                  # slab, drops counted exactly
    adaptive_cap: bool = False    # close the loop: a RouteCapController
                                  # (core.policy) adapts cap_factor off the
                                  # route_spill/route_drop feedback at poll
                                  # boundaries, replacing the static value
                                  # (multi-tenant only)
    prefix_cache: bool = False    # block-prefix reuse + LRU page eviction
                                  # (serving/eviction.py); opt-in — off,
                                  # admission always prefills from scratch
                                  # and frees reclaim every page
    prefix_backend: str = "linear"  # fingerprint-index backend (the macro
                                  # bench runs "chain" — bench_attack's
                                  # collision surface)
    prefix_capacity: int = 0      # fingerprint-index capacity (0: 4*n_pages)
    evict_batch: int = 8          # max victims per evict-on-pressure pass
    prefix_kw: tuple = ()         # extra backend kwargs as (key, value)
                                  # pairs (frozen-hashable), e.g.
                                  # (("nbuckets", 64),) for chain


def paged_decode_step(params: dict, cfg: ArchConfig, kv: PagedKV,
                      seq_ids: jax.Array, tokens: jax.Array,
                      lengths: jax.Array, active: jax.Array,
                      n_blocks: int):
    """One decode step for all slots. tokens/lengths/active: [B].
    Returns (logits [B, V], kv')."""
    x = transformer.embed(tokens[:, None], params["embed"], scale=cfg.embed_scale)
    positions = lengths[:, None]                            # [B,1]
    stack = params["attn_stack"]
    flags = transformer._attn_flags(cfg)
    safe_ids = jnp.where(active, seq_ids, 0)

    # page-table work is layer-independent: allocate the new block (if the
    # position opens one) and resolve the write target ONCE
    ps = kv.page_size
    blk, off = lengths // ps, lengths % ps
    kv, _ = kvcache.alloc_pages(kv, safe_ids, blk, active & (off == 0))
    pages_w, found_w = kvcache.resolve_blocks_at(kv, safe_ids, blk)
    pg = jnp.where(found_w & active, pages_w, kv.n_pages)   # OOB -> dropped

    def body(carry, sl):
        x, pool_k, pool_v = carry
        p, fl, layer = sl
        h = rms_norm(x, p["ln1"])
        qkn = (p["q_norm"], p["k_norm"]) if cfg.qk_norm else None
        q, k, v = project_qkv(h, p["wq"], p["wk"], p["wv"], qk_norm_scale=qkn)
        q = apply_rope(q, positions, fl["theta"])
        k = apply_rope(k, positions, fl["theta"])
        pool_k = pool_k.at[layer, pg, off].set(k[:, 0], mode="drop")
        pool_v = pool_v.at[layer, pg, off].set(v[:, 0], mode="drop")
        kv2 = kvcache.replace(kv, pool_k=pool_k, pool_v=pool_v)
        o = kvcache.paged_decode_attention(
            kv2, layer, q[:, 0], safe_ids, lengths + 1, n_blocks,
            window=fl["window"], softcap=cfg.attn_softcap)
        x = x + jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
        h2 = rms_norm(x, p["ln2"])
        y = swiglu(h2, p["wg"], p["wu"], p["wd"])
        return (x + y, pool_k, pool_v), None

    n = len(flags["window"])
    (x, pool_k, pool_v), _ = jax.lax.scan(
        body, (x, kv.pool_k, kv.pool_v),
        (stack, flags, jnp.arange(n, dtype=I32)))
    kv = kvcache.replace(kv, pool_k=pool_k, pool_v=pool_v)
    x = rms_norm(x, params["final_norm"])
    w = transformer.unembed_matrix(params, cfg)
    logits = jnp.einsum("bsd,dv->bsv", x, w).astype(F32)[:, 0]
    if cfg.logit_softcap > 0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits, kv


@dataclass
class ServingEngine:
    params: dict
    cfg: ArchConfig
    sc: ServeConfig
    kv: PagedKV = None
    queue: list = field(default_factory=list)     # list[(seq_id, prompt np.array)]
    finished: dict = field(default_factory=dict)  # seq_id -> list[int]
    rehashes: int = 0
    router_spills: int = 0        # cumulative tenant-router overflow keys
    router_drops: int = 0         # cumulative keys a compact slab dropped
    cap_ctl: elastic.RouteCapController | None = None  # adaptive cap loop
    cache_lookups: int = 0        # prefix-cache: blocks probed at admission
    cache_hits: int = 0           # prefix-cache: blocks adopted
    publishes: int = 0            # prefix-cache: blocks published
    _next_id: int = 1

    def __post_init__(self):
        c, s = self.cfg, self.sc
        self.kv = kvcache.make(c.n_layers, s.page_size, s.n_pages,
                               c.n_kv_heads, c.head_dim,
                               max_blocks=s.max_blocks, dtype=jnp.dtype(c.dtype),
                               n_tenants=s.n_tenants, cap_factor=s.cap_factor,
                               spill_slack=s.spill_slack,
                               prefix_cache=s.prefix_cache,
                               prefix_backend=s.prefix_backend,
                               prefix_capacity=s.prefix_capacity or None,
                               evict_batch=s.evict_batch,
                               prefix_kw=dict(s.prefix_kw))
        self._tenant_epochs0 = (np.asarray(
            jax.device_get(self.kv.table.epoch)) if s.n_tenants > 1 else None)
        # armed hysteresis latches for the elastic rehash trigger
        # (core.policy.rehash_wanted): a hot tenant rehashes once per load
        # excursion instead of on every poll above the threshold
        self._armed = True
        self._tenant_armed = np.ones((s.n_tenants,), bool)
        if s.n_tenants > 1:
            # one fused poll -> ONE host sync per decode step (live/tomb
            # loads + router spill/drop counters + rebuilding flags +
            # epochs)
            self._tenant_poll = jax.jit(lambda kv: (
                *kvcache.table_health(kv), kv.route_spill, kv.route_drop,
                kv.table.rebuilding, kv.table.epoch))
            if s.adaptive_cap:
                # spill-feedback adaptive cap: the controller walks
                # cap_factor along its geometric ladder off the SAME poll;
                # q_ref is the worst routed batch the engine issues
                # (free_sequences routes max_blocks keys per finished seq)
                self.cap_ctl = elastic.RouteCapController(
                    n_shards=s.n_tenants,
                    q_ref=s.max_seqs * s.max_blocks,
                    cap_factor=s.cap_factor, spill_slack=s.spill_slack)
        else:
            self._single_poll = jax.jit(lambda kv: (
                *kvcache.table_health(kv), kv.table.rebuilding,
                dhash.rebuild_done(kv.table)))
        b = s.max_seqs
        self.seq_ids = np.zeros((b,), np.int32)
        self.lengths = np.zeros((b,), np.int32)
        self.active = np.zeros((b,), bool)
        self.cur_tok = np.zeros((b,), np.int32)
        self.new_count = np.zeros((b,), np.int32)
        self.outputs: dict[int, list[int]] = {}
        self._step = jax.jit(partial(paged_decode_step, cfg=self.cfg,
                                     n_blocks=s.max_blocks))
        self._rehash = jax.jit(kvcache.rehash_step)
        self._free = jax.jit(kvcache.free_sequences, static_argnums=2)
        if s.prefix_cache:
            self._adopt = jax.jit(kvcache.adopt_prefix)
            self._publish = jax.jit(kvcache.publish_blocks)
            # fixed [max_blocks*ps] token pad -> [max_blocks] fingerprints:
            # one compile regardless of prompt length
            self._fps = jax.jit(lambda toks: prefix_cache.prefix_fingerprints(
                toks[None, :], s.page_size)[0])

    # -- request lifecycle ---------------------------------------------------
    def submit(self, prompt: list[int], tenant: int | None = None) -> int:
        """Queue a prompt; optional ``tenant`` pins the request to a tenant
        by advancing the id to the right residue class (ids stay unique and
        increasing — the partition is still ``seq_id % n_tenants``)."""
        sid = self._next_id
        if tenant is not None and self.sc.n_tenants > 1:
            sid += (tenant - sid) % self.sc.n_tenants
        self._next_id = sid + 1
        self.queue.append((sid, np.asarray(prompt, np.int32)))
        return sid

    def _admit(self):
        for slot in np.where(~self.active)[0]:
            if not self.queue:
                break
            sid, prompt = self.queue.pop(0)
            self._prefill(slot, sid, prompt)

    def _prefill(self, slot: int, sid: int, prompt: np.ndarray):
        """Prefill token-by-token through the paged step (simple, exact).
        Only THIS slot is active during its prefill — other in-flight
        sequences must not advance (their KV writes are masked and their
        lengths untouched).

        With the prefix cache enabled, admission first adopts the longest
        cached block-prefix (pages mapped + pinned, prefill skips those
        tokens — the cache hit is paid back as admission latency), and the
        freshly prefilled full blocks are published at the end.  Only
        blocks covered by ``prompt[:-1]`` take part: the last prompt token
        always runs through the decode step, so a published block is
        always fully written."""
        self.seq_ids[slot] = sid
        self.new_count[slot] = 0
        self.outputs[sid] = []
        start, fps, valid = 0, None, None
        if self.kv.prefix is not None:
            ps = self.sc.page_size
            n_pub = (len(prompt) - 1) // ps
            pad = np.zeros((self.sc.max_blocks * ps,), np.int32)
            pad[:len(prompt)] = prompt
            fps = self._fps(jnp.asarray(pad))
            valid = jnp.arange(self.sc.max_blocks) < n_pub
            self.kv, n_adopt, _ = self._adopt(
                self.kv, jnp.asarray(sid, np.int32), fps, valid)
            n_adopt = int(jax.device_get(n_adopt))
            self.cache_lookups += n_pub
            self.cache_hits += n_adopt
            start = n_adopt * ps
        self.lengths[slot] = start
        saved = self.active.copy()
        self.active[:] = False
        self.active[slot] = True
        for t in prompt[start:-1]:
            self.cur_tok[slot] = t
            self._run_slots(sample=False)
        if self.kv.prefix is not None:
            self.kv, n_ok = self._publish(
                self.kv, jnp.asarray(sid, np.int32), fps, valid)
            self.publishes += int(jax.device_get(n_ok))
        self.active = saved
        self.active[slot] = True
        self.cur_tok[slot] = prompt[-1]

    # -- stepping -------------------------------------------------------------
    def _run_slots(self, sample: bool = True):
        # copies: on the CPU backend jnp.asarray can alias a numpy buffer,
        # and the slot arrays are mutated in place while this step may
        # still be running asynchronously
        sids = jnp.asarray(self.seq_ids.copy())
        toks = jnp.asarray(self.cur_tok.copy())
        lens = jnp.asarray(self.lengths.copy())
        act = jnp.asarray(self.active.copy())
        logits, self.kv = self._step(self.params, kv=self.kv, seq_ids=sids,
                                     tokens=toks, lengths=lens, active=act)
        self.lengths = np.where(self.active, self.lengths + 1, self.lengths)
        self.kv = self._rehash(self.kv)            # background rebuild progress
        if sample:
            nxt = np.asarray(jax.device_get(jnp.argmax(logits, -1)), np.int32)
            return nxt
        return None

    def step(self):
        """One engine step: decode all active slots, harvest, admit."""
        self._admit()
        if not self.active.any():
            return False
        nxt = self._run_slots(sample=True)
        for slot in np.where(self.active)[0]:
            sid = int(self.seq_ids[slot])
            self.outputs[sid].append(int(nxt[slot]))
            self.cur_tok[slot] = nxt[slot]
            self.new_count[slot] += 1
            done = (self.new_count[slot] >= self.sc.max_new_tokens
                    or int(self.lengths[slot]) >= self.sc.max_blocks * self.sc.page_size - 1)
            if done:
                self.finished[sid] = self.outputs.pop(sid)
                self.kv = self._free(self.kv, jnp.asarray([sid], np.int32),
                                     self.sc.max_blocks)
                self.active[slot] = False
        self._maybe_rehash()
        return True

    def run(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or self.active.any()) and steps < max_steps:
            self.step()
            steps += 1
        return steps

    # -- live rehash ----------------------------------------------------------
    def _maybe_rehash(self):
        """Elastic rehash trigger (``core.policy.rehash_wanted``): fire when
        the live load crosses ``sc.rehash_load_factor`` OR tombstone churn
        (freed sequences) crosses the reclaim threshold, latched by an
        armed-hysteresis bit so a hot table rehashes once per excursion —
        the manual always-refire load check this replaces restarted a
        same-shape rehash on every poll while the load stayed high."""
        if self.sc.n_tenants > 1:
            return self._maybe_rehash_tenants()
        live, tomb, rebuilding, done = (
            np.asarray(x)
            for x in jax.device_get(self._single_poll(self.kv)))
        if bool(rebuilding):
            if bool(done):
                self.kv = kvcache.replace(
                    self.kv, table=dhash.rebuild_finish(self.kv.table))
                self.rehashes += 1
            return
        want, self._armed = elastic.rehash_wanted(
            float(live), float(tomb), self._armed, False,
            grow_load=self.sc.rehash_load_factor)
        if want:
            self.kv = kvcache.replace(
                self.kv, table=dhash.rebuild_start(self.kv.table,
                                                   seed=self.rehashes + 1))

    def _maybe_rehash_tenants(self):
        """Per-tenant elastic rehash over the page-table stack: each tenant
        has its own armed latch, so only tenants whose load/tombstone churn
        degraded start an epoch — and only once per excursion.  Completed
        epochs swap on-device inside ``kvcache.rehash_step``; no host-side
        finish is needed.  ``rehashes`` counts COMPLETIONS (epoch deltas
        across the stack) — the same semantics as the single-tenant path.
        The same poll surfaces the router spill/drop counters
        (``router_spills`` / ``router_drops``) so skewed tenant traffic
        leaning on the spill slab is observable separately from table
        load — and, with ``sc.adaptive_cap``, FEEDS the
        ``RouteCapController``: the controller walks ``cap_factor`` along
        its watermarked ladder and the new cap (static table metadata) is
        applied via ``kvcache.replace`` — recompiles are bounded by the
        ladder's finite value set."""
        loads, tombs, spill, drop, rebuilding, epochs = (
            np.asarray(x) for x in jax.device_get(self._tenant_poll(self.kv)))
        self.router_spills = int(spill.sum())
        self.router_drops = int(drop.sum())
        self.rehashes = int((epochs - self._tenant_epochs0).sum())
        if self.cap_ctl is not None:
            new_cap = self.cap_ctl.update(self.router_spills,
                                          self.router_drops)
            if new_cap != self.kv.cap_factor:
                self.kv = kvcache.replace(self.kv, cap_factor=new_cap)
        want, self._tenant_armed = elastic.rehash_wanted(
            loads, tombs, self._tenant_armed, rebuilding,
            grow_load=self.sc.rehash_load_factor)
        if want.any():
            self.kv = kvcache.start_rehash(self.kv, jnp.asarray(want))

    # -- prefix cache ---------------------------------------------------------
    def prefix_rehash(self, seed: int | None = None):
        """Start a live re-seed rehash of the fingerprint index (collision
        attack response); decode steps drive it via ``kvcache.rehash_step``
        and the epoch swaps on-device when done."""
        self.kv = kvcache.start_prefix_rehash(self.kv, seed=seed)

    @property
    def prefix_epoch(self) -> int:
        """Completed fingerprint-index rehash epochs."""
        return int(jax.device_get(self.kv.prefix.table.epoch))

    @property
    def evictions(self) -> int:
        """Cumulative prefix-cache pages evicted under pool pressure."""
        return int(jax.device_get(self.kv.prefix.evictions))

    @property
    def alloc_fails(self) -> int:
        """Masked page allocations that found no free page (must stay 0
        while eviction keeps up with demand)."""
        return int(jax.device_get(self.kv.alloc_fail))
