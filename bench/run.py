"""Run one cell of ``BENCHMARK.json`` once on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Earlier lines are JSON objects of the run's phases (the device, the op set
that ran, set-up and window facts); the last line of standard output is the
result, and the last lines of standard error are the numbers compared with
their limits.  Off a TPU, or with fewer chips than the cell asks for, it
exits 1 and prints no result.  JAX's persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` where that is set, else ``.jax_cache`` in
the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from bench import cells, harness, roofline  # noqa: E402


def prepare(jax) -> None:
    """The program importable (a checkout without it fails here, before any
    line is printed), and its compile cache at its fixed place, keeping
    every program however quick its compile."""
    sys.path.insert(0, str(cells.ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    devices = devices[: cell.chips]
    roofline.peak(devices[0].device_kind)
    prepare(jax)
    harness.emit(phase="device", platform=devices[0].platform,
                 device_kind=devices[0].device_kind, devices=len(devices),
                 compile_cache=jax.config.jax_compilation_cache_dir)
    out = harness.run(cell, args.seed % (1 << 63), args.seconds,
                      bool(args.trace), t_start=T_START, devices=devices,
                      compiles=harness.Compiles())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
