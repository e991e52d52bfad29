import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM and unsupported collectives all fail HERE.
Roofline terms (EXPERIMENTS.md §Roofline) are derived from each cell's
compiled artifact.

Usage:
  python -m repro.launch.dryrun                      # all cells, both meshes
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --list
Results land in benchmarks/results/dryrun/<mesh>_<arch>_<shape>.json.
"""

import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import ArchConfig
from repro.launch import analysis, shapes as shp
from repro.launch.mesh import make_production_mesh
from repro.optim.optimizer import OptConfig

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


def _opt_cfg() -> OptConfig:
    return OptConfig(total_steps=10_000)


def lower_train(cfg: ArchConfig, sp: shp.ShapeSpec, mesh):
    from repro.models.sharding import activation_ctx
    from repro.train import train_step as ts
    state, sshard = shp.state_struct(cfg, mesh, _opt_cfg())
    batch, bshard = shp.batch_struct(cfg, sp, mesh)
    fn = partial(ts.train_step, cfg=cfg, opt_cfg=_opt_cfg())
    with mesh, activation_ctx(mesh):
        jitted = jax.jit(fn, in_shardings=(sshard, bshard),
                         donate_argnums=0)
        return jitted.lower(state, batch)


def lower_prefill(cfg: ArchConfig, sp: shp.ShapeSpec, mesh):
    from repro.models import model, transformer, sharding as shard_lib

    def prefill(params, batch):
        hidden, _ = transformer.forward_train(params, cfg, batch)
        w = transformer.unembed_matrix(params, cfg)
        logits = jnp.einsum("bd,dv->bv", hidden[:, -1], w)
        return logits

    params = jax.eval_shape(partial(transformer.init_params, cfg),
                            jax.random.PRNGKey(0))
    pshard = shard_lib.param_shardings(params, mesh, fsdp=cfg.fsdp)
    batch, bshard = shp.batch_struct(cfg, sp, mesh)
    batch.pop("labels"), bshard.pop("labels")
    with mesh, shard_lib.activation_ctx(mesh):
        jitted = jax.jit(prefill, in_shardings=(pshard, bshard))
        return jitted.lower(params, batch)


def lower_decode(cfg: ArchConfig, sp: shp.ShapeSpec, mesh):
    from repro.models import model, transformer, sharding as shard_lib

    def serve_step(params, cache, tok):
        return model.decode_logits(params, cfg, tok, cache)

    params = jax.eval_shape(partial(transformer.init_params, cfg),
                            jax.random.PRNGKey(0))
    pshard = shard_lib.param_shardings(params, mesh, fsdp=cfg.fsdp)
    cache, cshard = shp.cache_struct(cfg, sp, mesh)
    tok, tshard = shp.decode_inputs(cfg, sp, mesh)
    with mesh, shard_lib.activation_ctx(mesh):
        jitted = jax.jit(serve_step, in_shardings=(pshard, cshard, tshard),
                         donate_argnums=1)
        return jitted.lower(params, cache, tok)


def lower_dhash_service(mesh, scfg=None):
    """The paper's own workload on the production mesh: a model-axis-sharded
    DHash service step (routed lookups/updates + one rebuild transition)."""
    import jax.tree_util as jtu
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import dhash, distributed as dd, hashing

    scfg = scfg or configs.get_config("dhash-paper")
    nshards = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    owner = hashing.fresh("tabulation", 7)
    d0 = dhash.make(scfg.backend, scfg.capacity_per_shard, chunk=scfg.chunk,
                    seed=0, fwd_hazard=getattr(scfg, "fwd_hazard", False))
    stacked = jtu.tree_map(
        lambda x: jax.ShapeDtypeStruct((nshards,) + x.shape, x.dtype), d0)
    tspec = jtu.tree_map(lambda _: P("model"), d0)
    q, u = scfg.lookups_per_step, scfg.updates_per_step
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    keys = {
        "lk": jax.ShapeDtypeStruct((nshards * q,), jnp.int32),
        "ik": jax.ShapeDtypeStruct((nshards * u,), jnp.int32),
        "iv": jax.ShapeDtypeStruct((nshards * u,), jnp.int32),
        "dk": jax.ShapeDtypeStruct((nshards * u,), jnp.int32),
    }

    @partial(jax.shard_map, mesh=mesh, check_vma=False,
             in_specs=(tspec, P("model"), P("model"), P("model"), P("model")),
             out_specs=(tspec, P("model")))
    def service(dstack, lk, ik, iv, dk):
        d = dd.peel(dstack)
        on = jnp.ones(ik.shape, bool)
        d, (found, _, ok_i, ok_d), _, _ = dd.routed_service_step(
            d, lk, ik, iv, dk, on, on, "model", owner)
        stats = jnp.stack([x.sum(dtype=jnp.int32) for x in (found, ok_i, ok_d)])
        return dd.unpeel(d), stats[None]

    with mesh:
        jitted = jax.jit(service,
                         in_shardings=(jtu.tree_map(lambda s: NamedSharding(mesh, s), tspec),
                                       *(NamedSharding(mesh, P("model")),) * 4),
                         donate_argnums=0)
        return jitted.lower(stacked, keys["lk"], keys["ik"], keys["iv"], keys["dk"])


def run_cell(arch: str, shape: str, mesh_kind: str, *, save: bool = True) -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = int(mesh.devices.size)
    t0 = time.time()
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                 "chips": chips}

    if arch == "dhash-paper":
        lowered = lower_dhash_service(mesh)
        model_flops = 0.0
        sp = None
    else:
        cfg = configs.get_config(arch)
        sp = shp.SHAPES[shape]
        skip = shp.applicability(cfg, shape)
        if skip:
            rec |= {"status": "skip", "reason": skip}
            if save:
                _save(rec)
            return rec
        lower = {"train": lower_train, "prefill": lower_prefill,
                 "decode": lower_decode}[sp.kind]
        lowered = lower(cfg, sp, mesh)
        n = cfg.param_count(active_only=True)
        if sp.kind == "train":
            tokens = sp.global_batch * sp.seq_len
            model_flops = 6 * n * tokens
        elif sp.kind == "prefill":
            tokens = sp.global_batch * sp.seq_len
            model_flops = 2 * n * tokens
        else:
            model_flops = 2 * n * sp.global_batch     # one token per seq

    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)

    # trip-count-aware per-chip HLO walk (xla cost_analysis does not
    # multiply while bodies; see hlo_cost.py) - shapes are per-device, so
    # walker numbers are per-chip; roofline divides global model_flops.
    from repro.launch import hlo_cost
    hlo = compiled.as_text()
    cost = hlo_cost.analyze(hlo)
    raw_flops, raw_bytes = analysis.cost_of(compiled)
    mem = analysis.memory_of(compiled)
    rl = analysis.Roofline(chips=chips, hlo_flops=cost.flops * chips,
                           hlo_bytes=cost.bytes * chips,
                           coll_bytes=cost.coll_bytes * chips,
                           model_flops=model_flops)
    rec |= {"status": "ok",
            "cost": {"flops_per_chip": cost.flops, "bytes_per_chip": cost.bytes,
                     "coll_bytes_per_chip": cost.coll_bytes,
                     "coll_detail": cost.coll, "coll_counts": cost.coll_counts,
                     "xla_raw_flops": raw_flops, "xla_raw_bytes": raw_bytes},
            "top_bytes": cost.top_bytes(10),
            "memory": mem, "roofline": rl.to_dict()}
    if save:
        _save(rec)
    return rec


def _save(rec: dict):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR,
                        f"{rec['mesh']}_{rec['arch']}_{rec['shape']}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)


def all_cells() -> list[tuple[str, str]]:
    cells = [(a, s) for a in configs.ARCH_IDS for s in shp.SHAPES]
    cells.append(("dhash-paper", "service"))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = all_cells()
    if args.arch:
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape:
        cells = [c for c in cells if c[1] == args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.list:
        for a, s in cells:
            print(a, s)
        return

    failures = []
    for a, s in cells:
        for mk in meshes:
            tag = f"{mk:6s} {a:24s} {s}"
            out = os.path.join(RESULTS_DIR, f"{mk}_{a}_{s}.json")
            if args.skip_existing and os.path.exists(out):
                print(f"[cached] {tag}")
                continue
            try:
                rec = run_cell(a, s, mk)
                if rec["status"] == "skip":
                    print(f"[ skip ] {tag}: {rec['reason']}")
                else:
                    rl = rec["roofline"]
                    print(f"[  ok  ] {tag}: {rec['compile_s']:.0f}s compile, "
                          f"bottleneck={rl['bottleneck']}, "
                          f"step={rl['step_time']*1e3:.1f}ms, mfu={rl['mfu']:.2f}")
            except Exception as e:
                failures.append((a, s, mk, repr(e)))
                print(f"[ FAIL ] {tag}: {e!r}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: "
                         + "; ".join(f"{a}/{s}/{m}" for a, s, m, _ in failures))
    print("ALL DRY-RUN CELLS PASSED")


if __name__ == "__main__":
    main()
