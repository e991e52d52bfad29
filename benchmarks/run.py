# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark aggregator: one entry per paper artifact + framework benches.

  fig2_throughput     paper Fig 2  (throughput vs batch width x load factor)
  fig3_rebuild        paper Fig 3  (rebuild time vs N)
  fig4_portability    paper Fig 4  (implementation-variant axis, see module)
  s62_oversubscribe   paper §6.2   (scaling past saturation)
  s1_attack           paper §1     (collision attack + live rebuild recovery
                                    + the bounded-probe cuckoo arm)
  moe_router          framework    (DHash hash-router rebalancing)
  kvcache_rehash      framework    (decode latency through live rehash)

CSV contract: name,us_per_call,derived
"""
from __future__ import annotations

import sys
import time


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.2f},{derived}")
    sys.stdout.flush()


def fig2_throughput():
    from benchmarks.bench_throughput import run
    for alpha in (20, 200):
        for mix in ((90, 5, 5), (80, 10, 10)):
            rows = run(alpha, mix, qs=(1024, 4096), steps=5, quiet=True)
            for name, a, m0, q, mops in rows:
                _row(f"fig2/{name}/a{a}/m{m0}/q{q}", 1.0 / mops,
                     f"{mops:.3f}Mops_s")


def fig3_rebuild():
    from benchmarks.bench_rebuild import run
    for name, n, dt in run(ns=(2_000, 8_000, 32_000), quiet=True):
        _row(f"fig3/{name}/n{n}", dt * 1e6, f"{dt*1e3:.1f}ms_full_rebuild")


def fig4_portability():
    from benchmarks.bench_portability import run
    for name, q, mops in run(alpha=20, qs=(1024, 4096), quiet=True):
        _row(f"fig4/{name}/q{q}", 1.0 / mops if mops else 0.0,
             f"{mops:.3f}Mops_s")


def s62_oversubscribe():
    from benchmarks.bench_oversubscribe import run
    for name, q, mops in run(qs=(512, 4096, 16384), quiet=True):
        _row(f"s62/{name}/q{q}", 1.0 / mops, f"{mops:.3f}Mops_s")


def elastic():
    from benchmarks.bench_oversubscribe import run_elastic
    r = run_elastic(quiet=True)
    for ph in ("steady", "burst", "drain", "recovered"):
        _row(f"elastic/{ph}/q{r['q']}", 1.0 / r[ph]["mops"],
             f"{r[ph]['mops']:.3f}Mops_s")
    _row("elastic/cliff", 0.0, f"{r['cliff_ratio']:.2f}x_of_steady")
    _row("elastic/resizes", 0.0,
         f"{r['grows']}grows_{r['shrinks']}shrinks_{r['flaps']}flaps_"
         f"load{r['final_load']:.2f}")


def s1_attack():
    from benchmarks.bench_attack import run
    r = run(quiet=True)
    for k, v in r.items():
        _row(f"attack/{k}", 1.0 / max(v, 1e-9), f"{v:.3f}Mlookups_s")


def moe_router():
    from benchmarks.bench_moe_router import run
    r = run(quiet=True)
    _row("moe_router/plain", r["t_plain"] * 1e6,
         f"imbalance{r['imb_before']:.2f}")
    _row("moe_router/dhash_overrides", r["t_table"] * 1e6,
         f"imbalance{r['imb_after']:.2f}")


def kvcache_rehash():
    from benchmarks.bench_kvcache import run
    r = run(quiet=True)
    _row("kvcache/decode_baseline", r["baseline_p50"] * 1e3, "p50")
    _row("kvcache/decode_during_rehash", r["during_p50"] * 1e3,
         f"p50_over_{r['rehash_steps']}steps")


def serve_macro():
    from benchmarks.bench_serve_macro import run
    r = run(quiet=True)
    for ph, p in r["phases"].items():
        _row(f"serve_macro/{ph}/p50", p["p50_ms"] * 1e3,
             f"p99_{p['p99_ms']:.1f}ms_miss{p['miss_rate']:.3f}_"
             f"evict{p['evictions']}")
    _row("serve_macro/attack_cacheop", 0.0,
         f"{r['attack_cacheop_x']:.1f}x_of_steady")
    _row("serve_macro/recovered_p99_ratio", 0.0,
         f"{r['recovered_p99_ratio']:.2f}")


def fused_probe():
    from benchmarks.bench_rebuild import run_fused_probe
    r = run_fused_probe(batch=4096, n_items=3_000, quiet=True)
    for name in ("fused", "unfused"):
        _row(f"fused_probe/{name}/q{r['batch']}", r[name]["wall_us"],
             f"{r[name]['sort']}sorts_{r[name]['pallas_call']}pallas")
    _row("fused_probe/pass_ratio", 0.0, f"{r['pass_ratio']:.2f}x_fewer_passes")


def fused_writes():
    from benchmarks.bench_rebuild import run_fused_writes
    r = run_fused_writes(batch=4096, n_items=3_000, quiet=True)
    for name in ("fused", "jnp"):
        _row(f"fused_writes/{name}/q{r['batch']}", r[name]["wall_us"],
             f"{r[name]['passes']}passes")
    _row("fused_writes/pass_ratio", 0.0,
         f"{r['pass_ratio']:.2f}x_fewer_passes")


def chain_fused():
    from benchmarks.bench_rebuild import run_chain_fused
    r = run_chain_fused(batch=4096, n_items=3_000, quiet=True)
    for name in ("fused", "jnp"):
        _row(f"chain_fused/{name}/q{r['batch']}", r[name]["wall_us"],
             f"{r[name]['passes']}passes")
    _row("chain_fused/pass_ratio", 0.0,
         f"{r['pass_ratio']:.2f}x_fewer_passes")


def table_stack():
    from benchmarks.bench_rebuild import run_table_stack
    r = run_table_stack(quiet=True)
    for name in ("stacked", "looped"):
        _row(f"table_stack/{name}/t{r['n_tables']}", r[name]["wall_us"],
             f"{r[name]['passes']}launches")
    _row("table_stack/pass_ratio", 0.0,
         f"{r['pass_ratio']:.2f}x_fewer_launches")


def routed_stack():
    from benchmarks.bench_rebuild import run_routed_stack
    r = run_routed_stack(quiet=True)
    for t in (8, 64):
        row = r[f"t{t}"]
        _row(f"routed_stack/t{t}/cap{row['cap']}+slab{row['spill_cap']}",
             row["wall_us"],
             f"{row['send_bytes_ratio']:.2f}x_fewer_send_bytes_"
             f"{row['overflow_rate']:.4f}_overflow_"
             f"{row['dropped_rate']:.4f}_dropped")
        # the adversarial 100%-skew arm: single-pass even under total skew
        _row(f"routed_stack/t{t}/adversarial", 0.0,
             f"{row['adversarial_sorts']}sorts_"
             f"{row['adversarial_pallas_calls']}pallas_no_retry")


TABLES = [fig2_throughput, fig3_rebuild, fig4_portability, s62_oversubscribe,
          elastic, s1_attack, moe_router, kvcache_rehash, serve_macro,
          fused_probe, fused_writes, chain_fused, table_stack, routed_stack]


def quick() -> None:
    """CI smoke mode: exercises the perf harness end-to-end in minutes —
    the fused-probe, fused-writes, chain-fused, table-stack,
    routed-stack (zipf + adversarial 100%-skew slab arms), elastic-burst,
    collision-attack, and serving-macro
    acceptance checks (pass counts + escape rates + resize/flap counts +
    recovery/latency ratios + their BENCH_*.json artifacts) plus a tiny
    fig3 rebuild sweep and a shrunk §6.2 oversubscription sweep so perf
    code can't silently rot."""
    print("name,us_per_call,derived")
    t0 = time.time()
    fused_probe()
    fused_writes()
    chain_fused()
    table_stack()
    routed_stack()
    elastic()
    s1_attack()                 # writes BENCH_attack.json (recover_ratio)
    serve_macro()               # writes BENCH_serve_macro.json
    from benchmarks.bench_oversubscribe import run as oversub_run
    for name, q, mops in oversub_run(alpha=20, qs=(512, 2048), quiet=True):
        _row(f"s62/{name}/q{q}", 1.0 / mops, f"{mops:.3f}Mops_s")
    from benchmarks.bench_rebuild import run as rebuild_run
    for name, n, dt in rebuild_run(ns=(2_000,), quiet=True):
        _row(f"fig3/{name}/n{n}", dt * 1e6, f"{dt*1e3:.1f}ms_full_rebuild")
    print(f"# quick done in {time.time()-t0:.0f}s", flush=True)


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: fused-probe acceptance + tiny fig3")
    args = ap.parse_args(argv)
    if args.quick:
        quick()
        return
    print("name,us_per_call,derived")
    for fn in TABLES:
        t0 = time.time()
        fn()
        print(f"# {fn.__name__} done in {time.time()-t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
