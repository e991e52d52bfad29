import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb driver: hypothesis -> change -> re-lower -> re-analyse.

Three cells (selection rationale in EXPERIMENTS.md §Perf):
  * rwkv6-3b  x train_4k   — worst roofline fraction (time-step scan stash)
  * gemma3-27b x train_4k  — most collective-bound (t_coll/t_mem highest)
  * dhash-paper x service  — the paper's own technique at scale

Each iteration lowers the SAME cell with one config change on the single-pod
mesh and reports the three roofline terms; results append to
benchmarks/results/perf_iterations.json.  The baseline rows come from the
full sweep (paper-faithful configs).
"""
import json
import time


from repro import configs
from repro.launch import analysis, hlo_cost, shapes as shp
from repro.launch.dryrun import lower_train, lower_dhash_service
from repro.launch.mesh import make_production_mesh

OUT = os.path.join(os.path.dirname(__file__), "results",
                   "perf_iterations.json")


def roofline_of(lowered, chips, model_flops):
    compiled = lowered.compile()
    cost = hlo_cost.analyze(compiled.as_text())
    rl = analysis.Roofline(chips=chips, hlo_flops=cost.flops * chips,
                           hlo_bytes=cost.bytes * chips,
                           coll_bytes=cost.coll_bytes * chips,
                           model_flops=model_flops)
    return rl, cost


def train_cell(arch, overrides, sp_name="train_4k"):
    cfg = configs.get_config(arch).scaled(**overrides)
    sp = shp.SHAPES[sp_name]
    mesh = make_production_mesh()
    lowered = lower_train(cfg, sp, mesh)
    mf = 6 * cfg.param_count(active_only=True) * sp.global_batch * sp.seq_len
    return roofline_of(lowered, mesh.devices.size, mf)


def service_cell(overrides):
    import dataclasses
    scfg = dataclasses.replace(configs.get_config("dhash-paper"), **overrides)
    mesh = make_production_mesh()
    lowered = lower_dhash_service(mesh, scfg)
    return roofline_of(lowered, mesh.devices.size, 0.0)


ITERATIONS = [
    # --- cell 1: rwkv6-3b train_4k (memory-bound: per-step scan stash) -----
    dict(cell="rwkv6-3b/train_4k", name="baseline",
         hypothesis="paper-faithful per-step wkv scan; bwd stashes one "
                    "f32[B,NH,HS,HS] state per timestep -> memory term "
                    "dominated by 4096-deep stash + per-step buffers",
         fn=lambda: train_cell("rwkv6-3b", {})),
    dict(cell="rwkv6-3b/train_4k", name="wkv_chunk128",
         hypothesis="remat the recurrence in 128-step chunks: stash shrinks "
                    "S/chunk=32x on states; per-step bwd buffers recomputed; "
                    "predict t_mem down >10x for ~1.5x extra recompute flops",
         fn=lambda: train_cell("rwkv6-3b", {"rwkv_chunk": 128})),
    dict(cell="rwkv6-3b/train_4k", name="wkv_chunk512",
         hypothesis="bigger chunks: fewer boundary states (8 saves) but "
                    "inner recompute span 512 -> more live per-chunk temps; "
                    "predict mild further t_mem change, direction unclear",
         fn=lambda: train_cell("rwkv6-3b", {"rwkv_chunk": 512})),
    # --- cell 2: gemma3-27b train_4k (collective-bound) ---------------------
    dict(cell="gemma3-27b/train_4k", name="baseline",
         hypothesis="3 separate q/k/v projections -> 3 bwd dx all-reduces of "
                    "[B,S,D] per layer; 2 more from gate/up; plus "
                    "remat-recomputed fwd psums",
         fn=lambda: train_cell("gemma3-27b", {})),
    dict(cell="gemma3-27b/train_4k", name="fused_qkv",
         hypothesis="one QKV matmul -> one dx AR instead of 3: predict "
                    "qkv-bwd AR bytes (2.6e11/chip, 37%% of coll) drop ~3x",
         fn=lambda: train_cell("gemma3-27b", {"fused_qkv": True})),
    dict(cell="gemma3-27b/train_4k", name="fused_qkv+gate_up",
         hypothesis="also fuse gate|up -> one dx AR instead of 2: predict "
                    "another ~8.7e10/chip off the collective term",
         fn=lambda: train_cell("gemma3-27b", {"fused_qkv": True,
                                              "fused_gate_up": True})),
    dict(cell="gemma3-27b/train_4k", name="fused+dots_remat",
         hypothesis="remat policy saves einsum outputs: kills the "
                    "recomputed fwd psums (1 AR/layer) and recompute flops, "
                    "trading activation memory; predict t_coll down ~15%%, "
                    "t_comp down ~25%%, t_mem up",
         fn=lambda: train_cell("gemma3-27b", {"fused_qkv": True,
                                              "fused_gate_up": True,
                                              "remat_policy": "dots"})),
    # --- cell 3: dhash-paper service (the paper's technique) ---------------
    dict(cell="dhash-paper/service", name="baseline",
         hypothesis="overflow-proof routing buffers [S,Q]: every shard "
                    "receives S*Q candidate slots though only Q/S are real "
                    "-> S x wasted probe work and wire bytes",
         fn=lambda: service_cell({})),
]


def main():
    rows = []
    for it in ITERATIONS:
        t0 = time.time()
        rl, cost = it["fn"]()
        rec = {"cell": it["cell"], "iter": it["name"],
               "hypothesis": it["hypothesis"], **rl.to_dict(),
               "compile_s": round(time.time() - t0, 1),
               "top_bytes": cost.top_bytes(6)}
        rows.append(rec)
        print(f"[{it['cell']:24s}] {it['name']:20s} "
              f"t_comp={rl.t_compute:8.3f} t_mem={rl.t_memory:8.3f} "
              f"t_coll={rl.t_collective:8.3f} mfu={rl.mfu:.4f} "
              f"({rec['compile_s']:.0f}s)", flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(rows, f, indent=1, default=float)
    print("wrote", OUT)


if __name__ == "__main__":
    main()
