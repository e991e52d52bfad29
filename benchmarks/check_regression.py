"""CI perf-regression gate: fresh BENCH_*.json vs the committed baselines.

Every benchmark that writes a ``BENCH_*.json`` artifact (bench_rebuild's
fused-probe, fused-writes, and chain-fused comparisons
today) commits its result at the repo root; CI snapshots those committed
files, re-runs ``benchmarks.run --quick``, and calls this script to diff
the fresh artifacts against the snapshot.

Gate semantics, per leaf key:

* **pass counts** (``sort``, ``pallas_call``, ``passes``) are STRUCTURAL:
  they come from jaxpr inspection, are machine-independent, and any
  increase is a regression — the fused paths grew an extra sort or kernel
  launch, or a jnp probe loop crept back in.  Compared exactly.  The
  elastic-burst scenario's **resize counts** (``grows``, ``shrinks``,
  ``flaps``) are STRUCTURAL for the same reason: the policy's watermark
  decisions are deterministic arithmetic over a pinned workload, so an
  extra resize — and above all a nonzero flap count, a resize fired
  inside a constant-population hold window — is a hysteresis regression,
  not noise.  ``attack_probe_bound`` (BENCH_attack) joins this class:
  the cuckoo arm's measured worst-case probe depth under the collision
  flood, capped at ``width - 1`` by the two-table layout — any increase
  is a layout regression, exact by construction.  ``adversarial_sorts`` /
  ``adversarial_pallas_calls`` (routed-stack bench) pin the single-pass
  spill-slab guarantee where it matters most: a 100%-one-tenant batch
  must still lower to 1 pallas_call and no sort — an increase means the
  full-width retry (or any second pass) crept back in.
  A gated key that is MISSING from the fresh artifact, or present with a
  non-numeric type, is itself a failure: a gate that silently skips what
  it cannot read is no gate.
* **pass ratios** (``pass_ratio``, ``send_bytes_ratio``,
  ``cliff_ratio``) must not drop by more than ``--ratio-tolerance``
  (default 15%): the fused-vs-jnp advantage, the slab router's wire-bytes
  reduction (full-width buffer bytes over primary+slab,
  Q/(cap + spill_cap) — the routed-stack bench, slab columns counted),
  and the elastic scenario's worst-phase-over-steady
  throughput floor are acceptance criteria.  ``cliff_ratio`` divides two
  min-of-steps walls from the SAME run, so host contention largely
  cancels out of it.  The attack/serving recovery ratios join this class:
  ``recover_ratio`` (BENCH_attack — post-rebuild over under-attack
  throughput, CAPPED at the bench's ``RECOVER_CAP`` so the O(chain)
  raw factor's jitter never gates; a drop means live-rebuild recovery
  broke), and the serving macro-bench's ``attack_p50_ratio`` /
  ``recovered_p50_ratio`` (steady-phase decode MEDIAN over attack- and
  recovered-phase medians, same-run numerators and denominators — decode
  must stay flat through a fingerprint-index collision attack and after
  the live rehash).  The macro-bench's p99 figures are reported but NOT
  gated: an extreme quantile of ~200 samples swings ~2x run-to-run on
  shared runners, which no fixed tolerance separates from regression.
* **escape rates** (``escape_rate``, ``overflow_rate``, ``miss_rate``,
  ``alloc_fail_rate``, ``dropped_rate``) are lower-is-better fractions —
  zipf-batch keys past their tenant's primary cap (routed-stack
  bench; slab pressure, deterministic for the pinned seed), keys past
  primary AND spill slab (``dropped_rate``, baseline 0.0: the slab is
  sized to serve the whole zipf spill — nonzero means the slab shrank or
  the accounting broke), the serving macro-bench's per-phase prefix-cache
  miss rate, and its page-allocation failure rate (baseline 0.0:
  eviction, not alloc failure, must absorb pool pressure).  They
  must not exceed the baseline by more than ``--rate-tolerance`` ABSOLUTE
  (default 0.02 — a 0.00 baseline allows up to 0.02, so benign hash-seed
  jitter passes but a coverage regression in the two-level tile map
  fails).
* **timings** (``wall_us``) must not grow by more than the artifact's
  wall-clock band.  All wall clocks follow the MIN-OF-5 protocol
  (``common.timeit``: five individually-synced repeats, minimum reported)
  — contention only ever adds time, so the min is the noise-robust
  estimator and the committed baselines carry far less run-to-run jitter
  than the old mean-of-N numbers.  The baselines are produced by a
  CI-runner-class container (same pinned deps, CPU interpret mode), and
  the workflow passes a CALIBRATED cross-runner band of 2.0: measured
  jitter of the interpreted kernels is <1.3x run-to-run on an idle
  machine and up to ~2.6x worst-case under scheduler contention, so a
  genuine slowdown past 3x fails while runner noise does not.  (The band
  was 3.0 — a >4x allowance — before the baselines were regenerated on
  runner-class hardware; min-of-5 is the ROADMAP's tightening step on
  top.)  **Per-artifact bands**: a baseline BENCH_*.json may carry a
  top-level ``"band"`` key overriding the global ``--time-tolerance`` for
  just that artifact — benchmarks whose measured jitter is tighter (or
  looser, e.g. host-dispatch-bound loops) than the fleet-wide 2.0 declare
  their own calibration where the number is produced, instead of holding
  every artifact to the worst common denominator.  The analogous
  top-level ``"ratio_band"`` key overrides ``--ratio-tolerance`` for one
  artifact's higher-is-better ratios: the serving macro-bench uses it
  because its per-phase p50 ratios common-mode out hardware speed but
  still swing ~±15% run to run in interpret mode (measured range
  0.81–1.08 on an idle box), while the regression it guards against —
  a blocking rehash — moves the ratio by ~50x, far past any band.

Exit status: 0 clean, 1 regression(s) found, 2 usage/setup error.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

STRUCTURAL = ("sort", "pallas_call", "passes", "grows", "shrinks", "flaps",
              "attack_probe_bound", "adversarial_sorts",
              "adversarial_pallas_calls")
RATIOS = ("pass_ratio", "send_bytes_ratio", "cliff_ratio", "recover_ratio",
          "attack_p50_ratio", "recovered_p50_ratio")
TIMINGS = ("wall_us",)
RATES = ("escape_rate", "overflow_rate", "miss_rate", "alloc_fail_rate",
         "dropped_rate")


def _compare(base, cur, path: str, failures: list[str], *,
             time_tol: float, ratio_tol: float, rate_tol: float) -> None:
    if isinstance(base, dict):
        if not isinstance(cur, dict):
            failures.append(f"{path}: expected object, got {type(cur).__name__}")
            return
        for k, v in base.items():
            if k not in cur:
                failures.append(f"{path}/{k}: missing from current run")
                continue
            _compare(v, cur[k], f"{path}/{k}", failures,
                     time_tol=time_tol, ratio_tol=ratio_tol,
                     rate_tol=rate_tol)
        return
    if isinstance(base, bool) or not isinstance(base, (int, float)):
        return  # strings/bools are descriptive, not gated
    key = path.rsplit("/", 1)[-1]
    gated = key in STRUCTURAL + RATIOS + RATES + TIMINGS
    if gated and (isinstance(cur, bool) or not isinstance(cur, (int, float))):
        # a gated metric that changed TYPE (a bench emitting "n/a"/null/a
        # nested object where the baseline has a number) must fail, not
        # skip: silently passing here is how a gate rots
        failures.append(
            f"{path}: gated metric is {type(cur).__name__} in the current "
            f"run, expected a number")
        return
    if key in STRUCTURAL:
        if cur > base:
            failures.append(
                f"{path}: pass count increased {base} -> {cur}")
    elif key in RATIOS:
        if cur < base * (1 - ratio_tol):
            failures.append(
                f"{path}: ratio regressed {base:.2f} -> {cur:.2f} "
                f"(tolerance {ratio_tol:.0%})")
    elif key in RATES:
        if cur > base + rate_tol:
            failures.append(
                f"{path}: escape rate regressed {base:.4f} -> {cur:.4f} "
                f"(absolute tolerance {rate_tol})")
    elif key in TIMINGS:
        if cur > base * (1 + time_tol):
            failures.append(
                f"{path}: timing regressed {base:.0f}us -> {cur:.0f}us "
                f"(tolerance {time_tol:.0%})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline-dir", required=True,
                    help="directory holding the committed BENCH_*.json")
    ap.add_argument("--current-dir", required=True,
                    help="directory holding the freshly emitted BENCH_*.json")
    ap.add_argument("--time-tolerance", type=float, default=0.15,
                    help="allowed relative wall-clock growth (default 0.15)")
    ap.add_argument("--ratio-tolerance", type=float, default=0.15,
                    help="allowed relative pass-ratio drop (default 0.15)")
    ap.add_argument("--rate-tolerance", type=float, default=0.02,
                    help="allowed ABSOLUTE escape-rate increase "
                         "(default 0.02)")
    args = ap.parse_args(argv)

    baseline_dir = pathlib.Path(args.baseline_dir)
    current_dir = pathlib.Path(args.current_dir)
    baselines = sorted(baseline_dir.glob("BENCH_*.json"))
    if not baselines:
        print(f"error: no BENCH_*.json baselines in {baseline_dir}",
              file=sys.stderr)
        return 2

    failures: list[str] = []
    for base_path in baselines:
        cur_path = current_dir / base_path.name
        if not cur_path.exists():
            failures.append(f"{base_path.name}: artifact not produced by the "
                            f"current run")
            continue
        base = json.loads(base_path.read_text())
        cur = json.loads(cur_path.read_text())
        band = base.get("band") if isinstance(base, dict) else None
        rband = base.get("ratio_band") if isinstance(base, dict) else None
        time_tol = float(band) if band is not None else args.time_tolerance
        ratio_tol = float(rband) if rband is not None \
            else args.ratio_tolerance
        _compare(base, cur, base_path.stem, failures,
                 time_tol=time_tol,
                 ratio_tol=ratio_tol,
                 rate_tol=args.rate_tolerance)
        suffix = "".join([f" (band {time_tol:.2f})" if band is not None
                          else "",
                          f" (ratio band {ratio_tol:.2f})"
                          if rband is not None else ""])
        print(f"checked {base_path.name}{suffix}")

    if failures:
        print(f"\nPERF REGRESSION: {len(failures)} failure(s)",
              file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"perf gate clean: {len(baselines)} artifact(s) within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
