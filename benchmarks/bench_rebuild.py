"""Paper Figure 3: rebuild time vs number of nodes.

Claims reproduced:
  * HT-Split resize is cheapest (bucket pointers only, no node movement);
  * HT-Xu rebuilds in one traversal (two-pointer-set advantage);
  * DHash and HT-RHT distribute every node -> time linear in N;
  * DHash beats HT-RHT because RHT re-walks each chain to its TAIL per node
    distributed (O(len^2) per bucket) while DHash distributes scan-order
    chunks;
  * the op mix running concurrently does not materially change rebuild time
    (predictability claim, §6.3).
"""
from __future__ import annotations

import argparse
import json
import pathlib

import numpy as np

from benchmarks.common import ALGOS, UNIVERSE, count_primitives, timeit

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def run(ns=(2_000, 8_000, 32_000), alpha=20, *, quiet=False):
    rows = []
    for n in ns:
        nbuckets = max(n // alpha, 16)
        rng = np.random.default_rng(0)
        present = rng.choice(UNIVERSE, size=n, replace=False).astype(np.int32)
        for name, cls in ALGOS.items():
            drv = cls(nbuckets, n, seed=1)
            drv.populate(present)
            drv.full_rebuild()            # warmup (compile)
            dt = min(drv.full_rebuild() for _ in range(2))
            rows.append((drv.name, n, dt))
            if not quiet:
                print(f"{drv.name:14s} N={n:<8d} rebuild {dt*1e3:9.1f} ms")
    # linearity check for DHash (paper: predictable, linear in N)
    ds = [(n, dt) for nm, n, dt in rows if nm.startswith("DHash")]
    if len(ds) >= 2:
        r = (ds[-1][1] / ds[0][1]) / (ds[-1][0] / ds[0][0])
        print(f"[summary] DHash rebuild-time linearity ratio "
              f"(time-growth / N-growth): {r:.2f} (1.0 = perfectly linear)")
    return rows


def run_fused_probe(batch=4096, n_items=3_000, *, iters=5, quiet=False,
                    out_path=None):
    """fused=on|off rebuild-epoch lookup comparison for the linear backend.

    The hot-path claim under test: with a rebuild in flight, the FUSED path
    executes ONE pallas_call per batch (the windowed linear kernels need no
    sort) where the unfused path pays one pallas_call per table plus a
    separate hazard pass.
    In interpret mode (no real TPU) the pass-count reduction is the
    acceptance metric (wall clock of interpreted Pallas is not meaningful);
    both are recorded in BENCH_fused_probe.json for the perf trajectory.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import buckets, dhash, hashing
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    d = dhash.make("linear", capacity=n_items, chunk=256, seed=1)
    present = rng.choice(UNIVERSE, size=n_items, replace=False).astype(np.int32)
    keys = jnp.asarray(present)
    ins = jax.jit(dhash.insert)
    for i in range(0, n_items, 4096):
        d, _ = ins(d, keys[i:i + 4096], keys[i:i + 4096])
    # put the table mid-rebuild with a populated hazard window
    d = dhash.rebuild_start(d, seed=9)
    d = jax.jit(dhash.rebuild_chunk)(d)
    d = jax.jit(dhash.rebuild_extract)(d)

    qs = jnp.asarray(np.concatenate([
        rng.choice(present, batch // 2),
        rng.integers(1, UNIVERSE, batch - batch // 2)]).astype(np.int32))
    h0o = hashing.bucket_of(d.old.hfn, qs, d.old.capacity)
    h0n = hashing.bucket_of(d.new.hfn, qs, d.new.capacity)
    args = ((d.old.key, d.old.val, d.old.state),
            (d.new.key, d.new.val, d.new.state),
            d.hazard_key, d.hazard_val, d.hazard_live, h0o, h0n, qs)

    mp = d.old.max_probes
    fused_fn = lambda *a: ops.ordered_lookup_fused(*a, max_probes=mp)   # noqa: E731
    unfused_fn = lambda *a: ops.ordered_lookup(*a, max_probes=mp)       # noqa: E731
    passes = {}
    for name, fn in (("fused", fused_fn), ("unfused", unfused_fn)):
        counts = count_primitives(jax.make_jaxpr(fn)(*args),
                                  ("sort", "pallas_call"))
        dt = timeit(fn, *args, warmup=1, iters=iters)
        passes[name] = dict(counts, wall_us=dt * 1e6)
        if not quiet:
            print(f"fused_probe/{name:8s} Q={batch} sorts={counts['sort']} "
                  f"pallas_calls={counts['pallas_call']} {dt*1e6:9.0f} us")
    # exactness cross-check while we're here
    f_f, v_f = fused_fn(*args)
    f_u, v_u = unfused_fn(*args)
    assert bool((f_f == f_u).all()) and bool((v_f == v_u).all())

    ratio = ((passes["unfused"]["sort"] + passes["unfused"]["pallas_call"])
             / (passes["fused"]["sort"] + passes["fused"]["pallas_call"]))
    result = {"batch": batch, "n_items": n_items, "interpret": True,
              "fused": passes["fused"], "unfused": passes["unfused"],
              "pass_ratio": ratio}
    assert passes["fused"]["sort"] == 0 and passes["fused"]["pallas_call"] == 1
    assert ratio >= 1.5, f"pass-count reduction regressed: {ratio:.2f}x"
    out = pathlib.Path(out_path) if out_path else _REPO_ROOT / "BENCH_fused_probe.json"
    out.write_text(json.dumps(result, indent=2) + "\n")
    if not quiet:
        print(f"[summary] fused pass-count reduction {ratio:.2f}x "
              f"(>=1.5x required) -> {out}")
    return result


def _count_passes(closed_jaxpr):
    """Serialized table-pass proxy for the write-path comparison.

    A "pass" is one serialized table-touching round: sorts, pallas_calls,
    and top-level gathers/scatters count 1 each; a scan whose body touches
    the table (the jnp probe/claim loops — ``fori_loop`` lowers to scan)
    counts its static ``length``, because each round is a *dependent* HBM
    gather that must land before the next slot can be probed.  A kernel's
    internal probe rounds run on a VMEM-resident slab inside its single
    pallas pass, and ``lax.cond`` branches are runtime-gated fallbacks the
    steady state never executes — neither is descended into.  This is the
    roofline distinction (see kernels/probe.py) the fused write path exists
    to exploit.
    """
    TABLE_OPS = ("sort", "gather", "scatter")

    def has_table_ops(jaxpr):
        for eq in jaxpr.eqns:
            if any(s in eq.primitive.name for s in TABLE_OPS):
                return True
            for p in eq.params.values():
                if hasattr(p, "jaxpr") and has_table_ops(
                        p.jaxpr if hasattr(p.jaxpr, "eqns") else p.jaxpr.jaxpr):
                    return True
        return False

    def rec(jaxpr):
        total = 0
        for eq in jaxpr.eqns:
            name = eq.primitive.name
            if name == "pallas_call":
                total += 1
                continue
            if name == "cond":
                continue
            if name == "scan":
                body = eq.params["jaxpr"].jaxpr
                if has_table_ops(body):
                    total += int(eq.params.get("length", 1))
                continue
            if name == "while":
                body = eq.params["body_jaxpr"].jaxpr
                total += 1 + rec(body)
                continue
            if any(s in name for s in TABLE_OPS):
                total += 1
            for p in eq.params.values():
                if hasattr(p, "jaxpr"):
                    total += rec(p.jaxpr if hasattr(p.jaxpr, "eqns")
                                 else p.jaxpr.jaxpr)
        return total

    return rec(closed_jaxpr.jaxpr)


def run_fused_writes(batch=4096, n_items=3_000, *, iters=5, quiet=False,
                     out_path=None):
    """fused=on vs jnp write-path comparison on the delete+rebuild mixed
    workload (PR 2 acceptance).

    One mid-rebuild step of the mixed workload = ordered lookup + insert
    (new table) + ordered DELETE + rebuild chunk EXTRACT + hazard LANDING.
    The fused arm runs the Pallas write kernels (``ordered_delete_fused``,
    ``extract_chunk_fused``, ``probe_insert`` for the landing); the jnp arm
    is the reference-oracle composition the unfused path executes.  The
    acceptance metric is the serialized table-pass reduction
    (``_count_passes``); interpreted-kernel wall clock is recorded for the
    trajectory but not asserted (interpret mode is not representative).
    Results land in BENCH_fused_writes.json; exactness of the fused arm is
    cross-checked against the jnp arm in-run.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import buckets, dhash, hashing
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    d = dhash.make("linear", capacity=n_items, chunk=256, seed=1, fused=True)
    present = rng.choice(UNIVERSE, size=n_items, replace=False).astype(np.int32)
    keys = jnp.asarray(present)
    ins = jax.jit(dhash.insert)
    for i in range(0, n_items, 4096):
        d, _ = ins(d, keys[i:i + 4096], keys[i:i + 4096])
    d = dhash.rebuild_start(d, seed=9)
    d = jax.jit(dhash.rebuild_chunk)(d)
    d = jax.jit(dhash.rebuild_extract)(d)   # populated hazard window

    mp = d.old.max_probes
    ch = d.chunk
    c_old, c_new = d.old.capacity, d.new.capacity
    qs = jnp.asarray(np.concatenate([
        rng.choice(present, batch // 2),
        rng.integers(1, UNIVERSE, batch - batch // 2)]).astype(np.int32))
    dk = jnp.asarray(np.concatenate([
        rng.choice(present, batch // 8),
        rng.integers(1, UNIVERSE, batch // 8)]).astype(np.int32))
    ik = jnp.asarray(rng.choice(
        np.arange(UNIVERSE, UNIVERSE + 10 * batch), batch // 4,
        replace=False).astype(np.int32))
    iv = ik * 3
    win_d = buckets.batch_winners(dk, jnp.ones(dk.shape, bool))
    win_i = buckets.batch_winners(ik, jnp.ones(ik.shape, bool))
    h0o_q = hashing.bucket_of(d.old.hfn, qs, c_old)
    h0n_q = hashing.bucket_of(d.new.hfn, qs, c_new)
    h0o_d = hashing.bucket_of(d.old.hfn, dk, c_old)
    h0n_d = hashing.bucket_of(d.new.hfn, dk, c_new)
    h0n_i = hashing.bucket_of(d.new.hfn, ik, c_new)
    hfn_new = d.new.hfn

    def fused_step(old_t, new_t, hk, hv, hl, cursor):
        f, v = ops.ordered_lookup_fused(old_t, new_t, hk, hv, hl,
                                        h0o_q, h0n_q, qs, max_probes=mp)
        os_, ns_, hl, ok_d = ops.ordered_delete_fused(
            old_t, new_t, hk, hv, hl, h0o_d, h0n_d, dk, win_d, max_probes=mp)
        old_t = (old_t[0], old_t[1], os_)
        new_t = (new_t[0], new_t[1], ns_)
        nk, nv, ns2, ok_i = ops.probe_insert(*new_t, h0n_i, ik, iv, win_i,
                                             max_probes=mp)
        new_t = (nk, nv, ns2)
        os2, hk2, hv2, hl2, cur2 = ops.extract_chunk_fused(
            old_t[0], old_t[1], old_t[2], cursor, chunk=ch)
        old_t = (old_t[0], old_t[1], os2)
        h0_h = hashing.bucket_of(hfn_new, hk2, c_new)
        lk2, lv2, ls2, _ = ops.probe_insert(*new_t, h0_h, hk2, hv2, hl2,
                                            max_probes=mp)
        return f, v, ok_d, ok_i, old_t[2], (lk2, lv2, ls2), cur2

    def jnp_step(old_t, new_t, hk, hv, hl, cursor):
        f, v = ref.ordered_lookup_ref(old_t, new_t, hk, hv, hl,
                                      h0o_q, h0n_q, qs, mp)
        os_, ok_o = ref.probe_delete_ref(old_t[0], old_t[1], old_t[2],
                                         h0o_d, dk, win_d, mp)
        pend = win_d & ~ok_o
        eq = (dk[:, None] == hk[None, :]) & hl[None, :]
        hz_hit = eq.any(-1) & pend
        kill = jnp.zeros_like(hl).at[
            jnp.where(hz_hit, jnp.argmax(eq, axis=-1), ch)].set(
            True, mode="drop")
        hl = hl & ~kill
        ns_, ok_n = ref.probe_delete_ref(new_t[0], new_t[1], new_t[2],
                                         h0n_d, dk, pend & ~hz_hit, mp)
        ok_d = ok_o | hz_hit | ok_n
        nk, nv, ns2, ok_i = ref.probe_insert_ref(
            new_t[0], new_t[1], ns_, h0n_i, ik, iv, win_i, mp)
        # extract (the jnp gather scan of linear_extract_chunk)
        pos = cursor + jnp.arange(ch, dtype=jnp.int32)
        valid = pos < c_old
        cpos = jnp.where(valid, pos, 0)
        live = valid & (os_[cpos] == 1)
        hk2 = jnp.where(live, old_t[0][cpos], 0)
        hv2 = jnp.where(live, old_t[1][cpos], 0)
        os2 = os_.at[jnp.where(live, cpos, c_old)].set(3, mode="drop")
        cur2 = jnp.minimum(cursor + ch, c_old)
        h0_h = hashing.bucket_of(hfn_new, hk2, c_new)
        lk2, lv2, ls2, _ = ref.probe_insert_ref(nk, nv, ns2, h0_h, hk2, hv2,
                                                live, mp)
        return f, v, ok_d, ok_i, os2, (lk2, lv2, ls2), cur2

    old_t = (d.old.key, d.old.val, d.old.state)
    new_t = (d.new.key, d.new.val, d.new.state)
    args = (old_t, new_t, d.hazard_key, d.hazard_val, d.hazard_live, d.cursor)

    passes, walls = {}, {}
    for name, fn in (("fused", fused_step), ("jnp", jnp_step)):
        passes[name] = _count_passes(jax.make_jaxpr(fn)(*args))
        walls[name] = timeit(jax.jit(fn), *args, warmup=1, iters=iters) * 1e6
        if not quiet:
            print(f"fused_writes/{name:5s} Q={batch} passes={passes[name]:4d} "
                  f"{walls[name]:9.0f} us")

    # exactness cross-check: both arms agree on every observable
    out_f = jax.jit(fused_step)(*args)
    out_j = jax.jit(jnp_step)(*args)
    assert bool((out_f[0] == out_j[0]).all())            # lookup found
    assert bool((out_f[1] == out_j[1]).all())            # lookup vals
    assert bool((out_f[2] == out_j[2]).all())            # delete ok
    assert bool((out_f[3] == out_j[3]).all())            # insert ok
    assert bool((out_f[4] == out_j[4]).all())            # old states
    assert int((out_f[5][2] == 1).sum()) == int((out_j[5][2] == 1).sum())
    assert int(out_f[6]) == int(out_j[6])                # cursor

    ratio = passes["jnp"] / passes["fused"]
    result = {"batch": batch, "n_items": n_items, "chunk": ch,
              "interpret": True,
              "workload": "lookup+insert+delete+extract+land (mid-rebuild)",
              "fused": {"passes": passes["fused"], "wall_us": walls["fused"]},
              "jnp": {"passes": passes["jnp"], "wall_us": walls["jnp"]},
              "pass_ratio": ratio}
    assert ratio >= 1.5, f"write-path pass reduction regressed: {ratio:.2f}x"
    out = (pathlib.Path(out_path) if out_path
           else _REPO_ROOT / "BENCH_fused_writes.json")
    out.write_text(json.dumps(result, indent=2) + "\n")
    if not quiet:
        print(f"[summary] fused write-path pass reduction {ratio:.2f}x "
              f"(>=1.5x required) -> {out}")
    return result


def run_chain_fused(batch=4096, n_items=3_000, *, iters=5, quiet=False,
                    out_path=None):
    """Arena-sorted chain backend, fused vs the pointer-chasing reference,
    on the mid-rebuild mixed workload (the PR 4 tentpole acceptance: the
    LAST backend onto the fused path).

    One mid-rebuild step = ordered lookup + ordered DELETE + insert (new
    table) + rebuild chunk EXTRACT + hazard LANDING.  The fused arm runs
    the chain kernels (``chain_ordered_lookup`` / ``chain_ordered_delete``
    / ``chain_insert_fused`` / ``extract_chunk_fused``) over the
    bucket-sorted arena; the jnp arm is the reference-oracle composition
    the unfused path executes (``ref.chain_*_ref`` — each pointer hop is a
    dependent arena gather, which is exactly what ``_count_passes`` charges
    for).  The acceptance metric is the serialized table-pass reduction
    (>= 1.5x gated); the per-op 1-sort/1-pallas_call budget is asserted as
    exact structural counts over the whole step (4 sorts + 5 pallas_calls:
    extract needs no sort).  Results land in BENCH_chain_fused.json;
    exactness of the fused arm is cross-checked against the jnp arm in-run.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import buckets, dhash, hashing
    from repro.core.struct_utils import replace
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    d = dhash.make("chain", capacity=int(n_items * 1.5), chunk=256, seed=1,
                   fused=True)
    present = rng.choice(UNIVERSE, size=n_items, replace=False).astype(np.int32)
    keys = jnp.asarray(present)
    ins = jax.jit(dhash.insert)
    for i in range(0, n_items, 4096):
        d, _ = ins(d, keys[i:i + 4096], keys[i:i + 4096])
    d = dhash.rebuild_start(d, seed=9)   # compacts the old arena
    d = jax.jit(dhash.rebuild_chunk)(d)
    d = jax.jit(dhash.rebuild_extract)(d)   # populated hazard window

    mc = d.old.max_chain
    ch = d.chunk
    nb_new = d.new.nbuckets
    arena_old = d.old.arena
    hfn_new = d.new.hfn
    qs = jnp.asarray(np.concatenate([
        rng.choice(present, batch // 2),
        rng.integers(1, UNIVERSE, batch - batch // 2)]).astype(np.int32))
    dk = jnp.asarray(np.concatenate([
        rng.choice(present, batch // 8),
        rng.integers(1, UNIVERSE, batch // 8)]).astype(np.int32))
    ik = jnp.asarray(rng.choice(
        np.arange(UNIVERSE, UNIVERSE + 10 * batch), batch // 4,
        replace=False).astype(np.int32))
    iv = ik * 3
    win_d = buckets.batch_winners(dk, jnp.ones(dk.shape, bool))
    win_i = buckets.batch_winners(ik, jnp.ones(ik.shape, bool))
    bqo_q = hashing.bucket_of(d.old.hfn, qs, d.old.nbuckets)
    bqn_q = hashing.bucket_of(hfn_new, qs, nb_new)
    bqo_d = hashing.bucket_of(d.old.hfn, dk, d.old.nbuckets)
    bqn_d = hashing.bucket_of(hfn_new, dk, nb_new)
    bqn_i = hashing.bucket_of(hfn_new, ik, nb_new)

    def fused_step(told, tnew, hk, hv, hl, cursor):
        po, pn = buckets._chain_parts(told), buckets._chain_parts(tnew)
        f, v = ops.chain_ordered_lookup(*po, *pn, hk, hv, hl, bqo_q, bqn_q,
                                        qs, max_chain=mc)
        os_, ns_, hl, ok_d = ops.chain_ordered_delete(
            *po, *pn, hk, hv, hl, bqo_d, bqn_d, dk, win_d, max_chain=mc)
        told = replace(told, astate=os_)
        tnew = replace(tnew, astate=ns_)
        pn = buckets._chain_parts(tnew)
        ak, av, ast, an, hd, ft, ok_i = ops.chain_insert_fused(
            pn[0], pn[1], pn[2], tnew.free_stack, tnew.free_top, bqn_i,
            ik, iv, win_i, max_chain=mc)
        tnew = replace(tnew, akey=ak, aval=av, astate=ast, anext=an,
                       heads=hd, free_top=ft)
        os2, hk2, hv2, hl2, cur2 = ops.extract_chunk_fused(
            told.akey, told.aval, told.astate, cursor, chunk=ch)
        told = replace(told, astate=os2)
        bq_h = hashing.bucket_of(hfn_new, hk2, nb_new)
        pn = buckets._chain_parts(tnew)
        ak, av, ast, an, hd, ft, _ = ops.chain_insert_fused(
            pn[0], pn[1], pn[2], tnew.free_stack, tnew.free_top, bq_h,
            hk2, hv2, hl2, max_chain=mc)
        tnew = replace(tnew, akey=ak, aval=av, astate=ast, anext=an,
                       heads=hd, free_top=ft)
        return f, v, ok_d, ok_i, told, tnew, cur2

    def jnp_step(told, tnew, hk, hv, hl, cursor):
        ol = (told.akey, told.aval, told.astate)
        olk = (told.anext, told.heads)
        nl = (tnew.akey, tnew.aval, tnew.astate)
        nlk = (tnew.anext, tnew.heads)
        f, v = ref.chain_ordered_lookup_ref(ol, olk, nl, nlk, hk, hv, hl,
                                            bqo_q, bqn_q, qs, mc)
        os_, ok_o = ref.chain_delete_ref(told.akey, told.aval, told.astate,
                                         told.anext, told.heads, bqo_d, dk,
                                         win_d, mc)
        pend = win_d & ~ok_o
        eq = (dk[:, None] == hk[None, :]) & hl[None, :]
        hz_hit = eq.any(-1) & pend
        kill = jnp.zeros_like(hl).at[
            jnp.where(hz_hit, jnp.argmax(eq, axis=-1), ch)].set(
            True, mode="drop")
        hl = hl & ~kill
        ns_, ok_n = ref.chain_delete_ref(tnew.akey, tnew.aval, tnew.astate,
                                         tnew.anext, tnew.heads, bqn_d, dk,
                                         pend & ~hz_hit, mc)
        ok_d = ok_o | hz_hit | ok_n
        ak, av, ast, an, hd, ft, ok_i = ref.chain_insert_ref(
            tnew.akey, tnew.aval, ns_, tnew.anext, tnew.heads,
            tnew.free_stack, tnew.free_top, bqn_i, ik, iv, win_i, mc)
        # extract (the jnp gather scan of chain_extract_chunk)
        pos = cursor + jnp.arange(ch, dtype=jnp.int32)
        valid = pos < arena_old
        cpos = jnp.where(valid, pos, 0)
        live = valid & (os_[cpos] == 1)
        hk2 = jnp.where(live, told.akey[cpos], 0)
        hv2 = jnp.where(live, told.aval[cpos], 0)
        os2 = os_.at[jnp.where(live, cpos, arena_old)].set(3, mode="drop")
        cur2 = jnp.minimum(cursor + ch, arena_old)
        told = replace(told, astate=os2)
        bq_h = hashing.bucket_of(hfn_new, hk2, nb_new)
        ak, av, ast, an, hd, ft, _ = ref.chain_insert_ref(
            ak, av, ast, an, hd, tnew.free_stack, ft, bq_h, hk2, hv2,
            live, mc)
        tnew = replace(tnew, akey=ak, aval=av, astate=ast, anext=an,
                       heads=hd, free_top=ft)
        return f, v, ok_d, ok_i, told, tnew, cur2

    args = (d.old, d.new, d.hazard_key, d.hazard_val, d.hazard_live,
            d.cursor)
    passes, walls, counts = {}, {}, {}
    for name, fn in (("fused", fused_step), ("jnp", jnp_step)):
        jx = jax.make_jaxpr(fn)(*args)
        passes[name] = _count_passes(jx)
        counts[name] = count_primitives(jx, ("sort", "pallas_call"))
        walls[name] = timeit(jax.jit(fn), *args, warmup=1, iters=iters) * 1e6
        if not quiet:
            print(f"chain_fused/{name:5s} Q={batch} passes={passes[name]:4d} "
                  f"{walls[name]:9.0f} us")

    # structural budget over the whole fused step: one sort + one
    # pallas_call per batch op (lookup, delete, insert, land), extract is
    # sort-free — 4 sorts + 5 pallas_calls, pinned exactly by the perf gate
    assert counts["fused"] == {"sort": 4, "pallas_call": 5}, counts["fused"]
    assert counts["jnp"]["pallas_call"] == 0

    # exactness cross-check: both arms agree on every per-query observable
    # and on the surviving membership (arena layouts differ only in the
    # landing order of the compacted vs position-aligned hazard chunk)
    out_f = jax.jit(fused_step)(*args)
    out_j = jax.jit(jnp_step)(*args)
    assert bool((out_f[0] == out_j[0]).all())            # lookup found
    assert bool((out_f[1] == out_j[1]).all())            # lookup vals
    assert bool((out_f[2] == out_j[2]).all())            # delete ok
    assert bool((out_f[3] == out_j[3]).all())            # insert ok
    assert bool((out_f[4].astate == out_j[4].astate).all())   # old arena
    assert int(out_f[6]) == int(out_j[6])                # cursor
    assert int(buckets.chain_count_live(out_f[5])) == \
        int(buckets.chain_count_live(out_j[5]))
    probe = jnp.concatenate([ik, qs[:512]])
    bq_p = hashing.bucket_of(hfn_new, probe, nb_new)

    def new_membership(tn):
        return ref.chain_lookup_ref(tn.akey, tn.aval, tn.astate, tn.anext,
                                    tn.heads, bq_p, probe, mc)

    f_f, v_f, _ = new_membership(out_f[5])
    f_j, v_j, _ = new_membership(out_j[5])
    np.testing.assert_array_equal(np.asarray(f_f), np.asarray(f_j))
    fm = np.asarray(f_j)
    np.testing.assert_array_equal(np.asarray(v_f)[fm], np.asarray(v_j)[fm])

    ratio = passes["jnp"] / passes["fused"]
    result = {"batch": batch, "n_items": n_items, "chunk": ch,
              "interpret": True,
              "workload": "lookup+insert+delete+extract+land (mid-rebuild, "
                          "chain backend)",
              "fused": {"passes": passes["fused"],
                        "wall_us": walls["fused"], **counts["fused"]},
              "jnp": {"passes": passes["jnp"], "wall_us": walls["jnp"]},
              "pass_ratio": ratio}
    assert ratio >= 1.5, f"chain fused pass reduction regressed: {ratio:.2f}x"
    out = (pathlib.Path(out_path) if out_path
           else _REPO_ROOT / "BENCH_chain_fused.json")
    out.write_text(json.dumps(result, indent=2) + "\n")
    if not quiet:
        print(f"[summary] chain fused pass reduction {ratio:.2f}x "
              f"(>=1.5x required) -> {out}")
    return result


def run_table_stack(n_tables=8, capacity=2048, batch=512, *, iters=5,
                    quiet=False, out_path=None):
    """``dhash.make_stack`` + vmapped ops vs a Python loop of independent
    tables (the multi-tenant serving seam; PR 5 tentpole acceptance).

    One engine step of T mid-rebuild tables = per table: lookup + insert +
    delete + one rebuild transition + the on-device epoch swap.  The
    STACKED arm runs it as ONE jitted program (``dhash.stack_*`` — every op
    is one vmapped kernel launch covering all T tables); the LOOPED arm
    dispatches T independent jitted single-table programs, which is what a
    multi-tenant server without the stack would do.

    The acceptance metric is the per-step LAUNCH-COUNT reduction: the
    looped arm issues T x (sorts + pallas_calls) of serialized launch
    traffic where the stacked arm issues the single-table count ONCE
    (vmap batches each sort/pallas_call over the [T] axis instead of
    re-issuing it), so the ratio is ~T and is gated >= 1.5.  On real
    accelerators per-launch cost is the multi-tenant throughput lever;
    interpreted-kernel wall clock is NOT representative (vmapped
    ``lax.cond`` executes both branches and interpret-mode Pallas cannot
    amortize launches), so both walls are recorded for the trajectory
    under this artifact's own wall band (``"band"`` key — the per-artifact
    calibration hook of check_regression) but the gate is structural.
    The fused per-table-step budget is asserted exactly: the vmapped
    rebuild-epoch ordered lookup stays ONE pallas_call (no sort) for the
    whole stack.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import backend, dhash

    rng = np.random.default_rng(0)
    t, half = n_tables, capacity // 2
    st = dhash.make_stack(t, "linear", capacity, chunk=256, seed=1,
                          fused=True)
    keys = jnp.asarray(rng.choice(UNIVERSE, size=(t, capacity),
                                  replace=False).astype(np.int32)) + 1
    st, _ = jax.jit(dhash.stack_insert)(st, keys[:, :half],
                                        keys[:, :half] * 3)
    st = jax.jit(dhash.stack_autostart)(st)          # every table mid-rebuild
    singles = dhash.unstack(st)
    lk = keys[:, :batch]
    ik = keys[:, half:half + batch]
    dk = keys[:, batch:2 * batch]

    def stacked_step(d, lk, ik, iv, dk):
        f, v = dhash.stack_lookup(d, lk)
        d, ok_i = dhash.stack_insert(d, ik, iv)
        d, ok_d = dhash.stack_delete(d, dk)
        d = dhash.stack_finish_same_shape(dhash.stack_rebuild_step(d))
        return d, (f, v, ok_i, ok_d)

    def single_step(d, lk, ik, iv, dk):
        f, v = dhash.lookup(d, lk)
        d, ok_i = dhash.insert(d, ik, iv)
        d, ok_d = dhash.delete(d, dk)
        d = dhash.finish_same_shape(dhash.rebuild_step(d))
        return d, (f, v, ok_i, ok_d)

    jstack = jax.jit(stacked_step)
    jsingle = jax.jit(single_step)

    # per-step launch traffic: the stacked arm's one program vs T programs
    names = ("sort", "pallas_call")
    c_stack = count_primitives(
        jax.make_jaxpr(stacked_step)(st, lk, ik, ik * 3, dk), names)
    c_single = count_primitives(
        jax.make_jaxpr(single_step)(singles[0], lk[0], ik[0], ik[0] * 3,
                                    dk[0]), names)
    launches_stacked = sum(c_stack.values())
    launches_looped = t * sum(c_single.values())
    ratio = launches_looped / launches_stacked

    # fused per-table-step budget, unchanged under vmap: the whole stack's
    # rebuild-epoch ordered lookup is ONE pallas_call and no sort
    be = backend.get("linear")
    ordered = jax.vmap(lambda d, k: be.ordered_lookup_fused(
        d.old, d.new, d.hazard_key, d.hazard_val, d.hazard_live, k,
        nres_cap=d.nres_cap))
    c_ordered = count_primitives(jax.make_jaxpr(ordered)(st, lk), names)
    assert c_ordered == {"sort": 0, "pallas_call": 1}, c_ordered

    def run_stacked():
        _d, out = jstack(st, lk, ik, ik * 3, dk)
        return out

    def run_looped():
        return [jsingle(singles[i], lk[i], ik[i], ik[i] * 3, dk[i])[1]
                for i in range(t)]

    wall_stacked = timeit(run_stacked, warmup=2, iters=iters) * 1e6
    wall_looped = timeit(run_looped, warmup=2, iters=iters) * 1e6

    # exactness: the stacked step and the looped steps agree per table
    out_s = jax.device_get(run_stacked())
    out_l = jax.device_get(run_looped())
    for i in range(t):
        for a, b in zip(out_s, out_l[i]):
            np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b))

    if not quiet:
        print(f"table_stack/stacked T={t} launches={launches_stacked:3d} "
              f"{wall_stacked:9.0f} us")
        print(f"table_stack/looped  T={t} launches={launches_looped:3d} "
              f"{wall_looped:9.0f} us")
    result = {"n_tables": t, "capacity": capacity, "batch": batch,
              "interpret": True, "band": 2.5,
              "workload": "lookup+insert+delete+rebuild_step+swap "
                          "(T mid-rebuild tables)",
              "stacked": {"passes": launches_stacked,
                          "wall_us": wall_stacked, **c_stack},
              "looped": {"passes": launches_looped, "wall_us": wall_looped},
              "ordered_lookup_budget": c_ordered,
              "pass_ratio": ratio}
    assert ratio >= 1.5, f"stack launch reduction regressed: {ratio:.2f}x"
    out = (pathlib.Path(out_path) if out_path
           else _REPO_ROOT / "BENCH_table_stack.json")
    out.write_text(json.dumps(result, indent=2) + "\n")
    if not quiet:
        print(f"[summary] stacked launch reduction {ratio:.2f}x over "
              f"{t}-table loop (>=1.5x required) -> {out}")
    return result


def run_routed_stack(batch=1024, capacity=1024, cap_factor=2.0, *, iters=5,
                     quiet=False, out_path=None):
    """Single-pass spill-slab tenant routing under zipf skew, T in {8, 64}.

    A flat [Q] key batch with zipf-distributed tenants (the suite's shared
    skew source, ``common.zipf_owners``) is grouped by the counting-sort
    router into ONE ``[T, cap + spill_cap]`` buffer — a per-tenant primary
    of ``cap = ceil(c*Q/T)`` columns plus a compact shared spill slab of
    ``spill_cap = ceil(slack*Q)`` columns — and served by ONE vmapped
    fused stack lookup.  There is no retry pass any more: spilled keys
    ride the slab in the same pass.  Gated in BENCH_routed_stack.json:

    * **send_bytes_ratio** (gated as a ratio, >= 1.5): full-width buffer
      bytes over the slab layout, Q/(cap + spill_cap) — the wire-bytes and
      scatter-work win.  The slab IS counted in the wire bytes; the win
      comes from a compact per-arm ``spill_slack`` sized so the zipf spill
      still fits (dropped_rate stays 0.0).
    * **per-op budget** (gated structurally): the slab-routed fused lookup
      lowers to exactly 1 ``sort`` + 1 ``pallas_call`` TOTAL — the router
      itself is sort-free (histogram + cumsum + 2-D scatter), the slab
      adds no pass, and the cond-gated retry is gone.
    * **adversarial budget** (``adversarial_sorts`` /
      ``adversarial_pallas_calls``, gated structurally): the SAME 1+1
      budget on a 100%-one-tenant batch served bit-identically to the
      full-width route through the overflow-proof slab.
    * **overflow_rate** (gated as a rate): fraction of the zipf batch past
      its tenant's primary cap — slab pressure, the signal the serving
      layer's RouteCapController consumes.  **dropped_rate** (gated as a
      rate): fraction past primary AND slab — exactly accounted, 0.0 for
      these arms by construction.

    Wall clocks are interpret-mode (recorded for the trajectory under this
    artifact's band, not the acceptance); correctness is asserted inline —
    the slab route serves EVERY key here (no drops) and agrees with the
    full-width route bit-for-bit.
    """
    import jax
    import jax.numpy as jnp

    from benchmarks.common import zipf_owners
    from repro.core import backend, dhash
    from repro.core import distributed as dd

    rng = np.random.default_rng(0)
    be = backend.get("linear")
    keys = jnp.asarray(rng.choice(UNIVERSE, size=batch,
                                  replace=False).astype(np.int32)) + 1
    # per-arm compact slack: sized so the deterministic zipf spill fits the
    # slab (dropped_rate 0.0) while the total width stays >= 1.5x under
    # full width.  t8 zipf spill = 333 <= 384; t64 spill = 592 <= 640.
    slack = {8: 0.375, 64: 0.625}
    result = {"batch": batch, "cap_factor": cap_factor, "interpret": True,
              "band": 2.5,
              "workload": "zipf(a=1.2)-skewed tenant lookups through the "
                          "single-pass spill-slab router, fused linear "
                          "stacks"}
    names = ("sort", "pallas_call")
    for t in (8, 64):
        tenant = jnp.asarray(zipf_owners(rng, batch, t))
        cap = dd.route_cap(cap_factor, batch, t)
        spill_cap = dd.route_spill_cap(batch, cap, slack[t])
        st = dhash.make_stack(t, "linear", capacity, chunk=256, seed=1,
                              fused=True)
        full = dd._route(keys, tenant, t)
        st, _ = jax.jit(dhash.stack_insert)(st, full.send, full.send * 3,
                                            full.smask)

        def routed(st, k, tn, sc):
            rt = dd._route(k, tn, t, cap, sc)
            f, v = jax.vmap(lambda d, kk: be.lookup_fused(d.old, kk))(
                st, rt.send)
            return (dd._unroute(f & rt.smask, rt, fill=False),
                    dd._unroute(v, rt, fill=0), rt.served, rt.overflow,
                    rt.dropped)

        # the acceptance budget: slab router + fused stack lookup = ONE
        # pallas_call and no sort total (the router and the windowed kernel
        # sort nothing; no cond retry exists)
        budget = count_primitives(
            jax.make_jaxpr(lambda s, k, tn: routed(s, k, tn, spill_cap))(
                st, keys, tenant), names)
        assert budget == {"sort": 0, "pallas_call": 1}, (t, budget)

        jrouted = jax.jit(routed, static_argnums=3)
        wall = timeit(lambda: jrouted(st, keys, tenant, spill_cap),
                      warmup=2, iters=iters) * 1e6
        f, v, served, overflow, dropped = (
            np.asarray(x) for x in jax.device_get(
                jrouted(st, keys, tenant, spill_cap)))
        # exact spill/drop accounting vs a host-side histogram
        hist = np.bincount(np.asarray(tenant), minlength=t)
        np.testing.assert_array_equal(overflow, np.maximum(hist - cap, 0))
        assert int(dropped.sum()) == max(int(overflow.sum()) - spill_cap, 0)
        # the slab serves every spilled key for these arms: all found,
        # values bit-identical to the full-width route
        assert served.all() and f.all(), (t, int(served.sum()))
        np.testing.assert_array_equal(v, np.asarray(keys) * 3)
        send_bytes_ratio = batch / (cap + spill_cap)
        overflow_rate = float(overflow.sum()) / batch
        dropped_rate = float(dropped.sum()) / batch
        assert send_bytes_ratio >= 1.5, \
            f"slab routing buffer win regressed: {send_bytes_ratio:.2f}x"
        assert dropped_rate == 0.0, \
            f"zipf arm must not drop: {dropped_rate:.4f}"

        # adversarial arm: 100% one-tenant skew through the overflow-proof
        # slab — same single pallas_call, bit-identical to full width
        atn = jnp.zeros((batch,), jnp.int32)
        adv_budget = count_primitives(
            jax.make_jaxpr(lambda s, k, tn: routed(s, k, tn, batch - cap))(
                st, keys, atn), names)
        assert adv_budget == {"sort": 0, "pallas_call": 1}, (t, adv_budget)
        fa, va, sa, _, da = (np.asarray(x) for x in jax.device_get(
            jrouted(st, keys, atn, batch - cap)))
        assert sa.all() and int(da.sum()) == 0
        # full-width reference: cap=Q serves everything in the primary
        rt_fw = dd._route(keys, atn, t, batch)
        f_fw, v_fw = jax.vmap(lambda d, kk: be.lookup_fused(d.old, kk))(
            st, rt_fw.send)
        f_fw = np.asarray(dd._unroute(f_fw & rt_fw.smask, rt_fw,
                                      fill=False))
        v_fw = np.asarray(dd._unroute(v_fw, rt_fw, fill=0))
        np.testing.assert_array_equal(fa, f_fw)
        np.testing.assert_array_equal(va[fa], v_fw[fa])

        if not quiet:
            print(f"routed_stack T={t:<3d} cap={cap:<5d} slab={spill_cap:<5d} "
                  f"send_bytes_ratio={send_bytes_ratio:5.2f}x "
                  f"overflow_rate={overflow_rate:.4f} "
                  f"dropped_rate={dropped_rate:.4f} {wall:9.0f} us")
        result[f"t{t}"] = {"n_tenants": t, "cap": cap,
                           "spill_cap": spill_cap, "spill_slack": slack[t],
                           "send_bytes_ratio": send_bytes_ratio,
                           "overflow_rate": overflow_rate,
                           "dropped_rate": dropped_rate,
                           "wall_us": wall, **budget,
                           "adversarial_sorts": adv_budget["sort"],
                           "adversarial_pallas_calls":
                               adv_budget["pallas_call"]}
    out = (pathlib.Path(out_path) if out_path
           else _REPO_ROOT / "BENCH_routed_stack.json")
    out.write_text(json.dumps(result, indent=2) + "\n")
    if not quiet:
        print(f"[summary] spill-slab routing: "
              f"{result['t8']['send_bytes_ratio']:.2f}x fewer wire bytes "
              f"at T=8, {result['t64']['send_bytes_ratio']:.2f}x at T=64, "
              f"0 drops, 1 pallas_call per routed op (adversarial "
              f"skew included, no retry) -> {out}")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ns", type=int, nargs="*", default=[2_000, 8_000, 32_000])
    ap.add_argument("--alpha", type=int, default=20)
    ap.add_argument("--fused", action="store_true",
                    help="also run the fused=on|off rebuild-epoch probe, "
                         "write-path, chain-backend, table-stack, and "
                         "routed-stack comparisons (writes "
                         "BENCH_fused_probe.json + BENCH_fused_writes.json "
                         "+ BENCH_chain_fused.json + "
                         "BENCH_table_stack.json + "
                         "BENCH_routed_stack.json)")
    args = ap.parse_args(argv)
    rows = run(tuple(args.ns), args.alpha)
    if args.fused:
        run_fused_probe()
        run_fused_writes()
        run_chain_fused()
        run_table_stack()
        run_routed_stack()
    return rows


if __name__ == "__main__":
    main()
