"""Paper §6.2 robustness claim: throughput past core saturation.

"When the number of worker threads exceeds the number of CPU cores, the
performance of DHASH increases slightly ... The performance of other
alternatives becomes flat or decreases due to the increased contention on
bucket locks."

SPMD mapping: batch width Q grows far beyond any fixed parallel resource;
DHash's per-op cost amortizes (vectorization), while the lock-modelled
tables' serialization rounds grow with Q/B and their throughput flattens or
falls.

``skew > 0`` draws lookup/delete keys from the suite's SHARED zipf skew
source (``common.zipf_owners`` — the same generator the routed-stack bench
uses for tenant load): hot-key concentration models the adversarial
popularity distribution the capped tenant router is gated under.
"""
from __future__ import annotations

import json
import pathlib
import time

import numpy as np

from benchmarks.common import ALGOS, UNIVERSE, Workload, run_throughput

_REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def run(alpha=200, qs=(512, 2048, 8192, 16384), *, skew=0.0, quiet=False):
    nbuckets = 64
    n = alpha * nbuckets
    rng = np.random.default_rng(0)
    present = rng.choice(UNIVERSE, size=n, replace=False).astype(np.int32)
    tag = f" zipf(a={skew})" if skew > 0 else ""
    rows = []
    for name in ("DHash", "HT-RHT", "HT-Xu"):
        drv = ALGOS[name](nbuckets, n, seed=1)
        drv.populate(present)
        series = []
        for q in qs:
            wl = Workload(q=q, mix=(80, 10, 10), skew=skew)
            mops = run_throughput(drv, wl, present, steps=4,
                                  rng=np.random.default_rng(q)) / 1e6
            series.append(mops)
            rows.append((drv.name, q, mops))
            if not quiet:
                print(f"{drv.name:14s} Q={q:<6d}{tag} {mops:8.3f} Mops/s")
        trend = series[-1] / series[0]
        if not quiet:
            print(f"[summary] {drv.name}{tag}: Q x{qs[-1]//qs[0]} -> "
                  f"throughput x{trend:.2f} "
                  f"({'scales' if trend > 1.5 else 'flat/degrades'})")
    return rows


def run_elastic(*, q=512, capacity0=1024, phase_steps=10, quiet=False,
                out_path=None):
    """Elastic burst scenario: steady -> burst -> drain -> recovered on one
    policy-driven ``DHashEngine``.

    The acceptance story from small_hash.c's trigger set: the load-factor
    watermarks grow the table under an insert burst and shrink it back after
    a drain, the hysteresis band keeps the boundary flap-free (``flaps`` is
    the count of resizes fired during the constant-population hold windows —
    STRUCTURAL, baseline 0), and the throughput cliff through the whole
    round trip stays above 0.5x steady state (``cliff_ratio`` — RATIO
    gated).  The jaxpr section proves the telemetry is free: the counted
    lookup and the policy step add ZERO sorts / pallas_calls over the plain
    fused lookup (STRUCTURAL).

    Emits BENCH_elastic.json for the CI perf gate.
    """
    import jax
    import jax.numpy as jnp

    from benchmarks.common import count_primitives
    from repro.core import dhash, engine, policy as elastic

    rng = np.random.default_rng(0)
    pol = elastic.make(min_capacity=64)
    eng = engine.DHashEngine(
        dhash.make("linear", capacity=capacity0, chunk=256, seed=1,
                   fused=False),
        policy=pol, poll_every=1)

    base = rng.choice(UNIVERSE, size=700, replace=False).astype(np.int32)
    burst = rng.choice(
        np.setdiff1d(rng.integers(1, UNIVERSE, 40_000).astype(np.int32),
                     base),
        size=phase_steps * q, replace=False).astype(np.int32)
    # never inserted: the population-neutral phases delete these (refused)
    absent = np.setdiff1d(rng.integers(1, UNIVERSE, 4 * q).astype(np.int32),
                          np.concatenate([base, burst]))[:q]
    assert absent.size == q
    none_k = np.zeros(q, np.int32)
    none_m = np.zeros(q, bool)
    all_m = np.ones(q, bool)

    # resize event log: "G"/"S" in firing order (poll_every=1 -> exact).
    # A FLAP is a direction reversal beyond the one expected grow->shrink
    # turn of the round trip; same-direction repeats (capacity chase under
    # a continuing burst) are legitimate.
    events: list[str] = []
    seen = [0, 0]

    def record():
        g, s = eng.stats.grows, eng.stats.shrinks
        events.extend("G" * (g - seen[0]) + "S" * (s - seen[1]))
        seen[0], seen[1] = g, s

    def drive(batch):
        out = eng.step(*batch)
        record()
        return out

    for i in range(0, base.size, q):          # populate + compile warmup
        pad = np.resize(base[i:i + q], q)
        m = np.zeros(q, bool)
        m[:min(q, base.size - i)] = True
        drive((pad, pad, pad, none_k, m, none_m))

    def phase(n_steps, make_batch):
        """Drive n_steps (lookup(q) + insert(q) + delete(q) each), timing
        every step individually; the phase throughput is the MIN-of-steps
        wall clock (the suite's min-of-N protocol): a resize mid-phase
        retraces the jitted step for the new table shape, and that one-time
        compile stall is not the steady per-step cost under test."""
        best = float("inf")
        for s in range(n_steps):
            t0 = time.perf_counter()
            jax.block_until_ready(drive(make_batch(s)))
            best = min(best, time.perf_counter() - t0)
        return 3 * q / best / 1e6, best   # Mops/s, seconds/step

    def lookups_only(s):
        lk = rng.choice(base, q).astype(np.int32)
        return (lk, none_k, none_k, none_k, none_m, none_m)

    # steady, burst and recovered each carry q lookups, q inserts and q
    # deletes, all unmasked (a masked-off batch costs nothing, and the rate
    # counts 3q operations), so they differ only in the table's state; where
    # the population must hold, the inserts are of present keys and the
    # deletes of absent ones, and both are refused.  No rehash runs then:
    # mid-epoch an insert only refuses keys of its target table.
    def neutral(present):
        def batch(s):
            ik = rng.choice(present, q).astype(np.int32)
            return (rng.choice(base, q).astype(np.int32), ik, ik, absent,
                    all_m, all_m)
        return batch

    phases = {}
    mops, dt = phase(phase_steps, neutral(base))         # steady state
    phases["steady"] = {"mops": mops}

    def burst_batch(s):
        ik = burst[s * q:(s + 1) * q]
        return (rng.choice(base, q).astype(np.int32), ik, ik, absent,
                all_m, all_m)

    mops, dt = phase(phase_steps, burst_batch)           # insert burst
    phases["burst"] = {"mops": mops}
    phase(phase_steps, lookups_only)                     # hold at burst load
    grows_burst = eng.stats.grows

    def drain_batch(s):
        dk = burst[s * q:(s + 1) * q]
        return (rng.choice(base, q).astype(np.int32), none_k, none_k, dk,
                none_m, all_m)

    mops, dt = phase(phase_steps, drain_batch)           # delete the burst
    # drain base too, down to a population far below the low watermark
    for i in range(0, 512, q):
        dk = base[i:i + q]
        drive((rng.choice(base, q).astype(np.int32), none_k, none_k,
               np.resize(dk, q), none_m,
               np.arange(q) < min(q, 512 - i)))
    phases["drain"] = {"mops": mops}

    # settle: the drain's tombstones first fire an on-device reclaim rehash
    # (same-shape, holds the rebuild trylock), and only then can the shrink
    # start + complete its own migration -- drive until it lands
    for _ in range(200):
        drive(lookups_only(0))
        if eng.stats.shrinks >= 1 and not bool(
                jax.device_get(eng.state.rebuilding)):
            break
    shrinks = eng.stats.shrinks
    mops, dt = phase(phase_steps, neutral(base[512:]))   # recovered steady
    phases["recovered"] = {"mops": mops}

    turns = sum(1 for a, b in zip(events, events[1:]) if a != b)
    flaps = max(0, turns - 1)   # one G->S turn IS the round trip
    cliff = min(p["mops"] for p in phases.values()) / phases["steady"]["mops"]
    from repro.core import backend as backends
    be = backends.get(eng.state.backend)
    final_slots = int(be.capacity_of(eng.state.old))
    final_live = int(jax.device_get(be.count_live(eng.state.old)))

    # -- jaxpr proof: telemetry + policy are pass-free (fused linear) -------
    df = dhash.make("linear", capacity=capacity0, seed=3, fused=True)
    ks = jnp.zeros((q,), jnp.int32)
    plain = count_primitives(
        jax.make_jaxpr(dhash.lookup)(df, ks), ("sort", "pallas_call"))
    counted = count_primitives(
        jax.make_jaxpr(lambda d, k: dhash.lookup_counted(d, k, probe_hi=7))(
            df, ks), ("sort", "pallas_call"))
    pol_f = elastic.make(in_place=True)
    pstep = count_primitives(
        jax.make_jaxpr(elastic.policy_step)(pol_f, df),
        ("sort", "pallas_call"))
    assert counted == plain, (counted, plain)
    assert pstep == {"sort": 0, "pallas_call": 0}, pstep

    result = {
        "q": q, "capacity0": capacity0, "phase_steps": phase_steps,
        "interpret": True, **phases,
        "cliff_ratio": cliff,
        "grows": int(eng.stats.grows), "shrinks": int(shrinks),
        "flaps": int(flaps), "resize_events": "".join(events),
        "final_slots": final_slots,
        "final_load": final_live / final_slots,
        "counted_lookup": counted, "plain_lookup": plain,
        "policy_step": pstep,
    }
    out = pathlib.Path(out_path) if out_path else _REPO_ROOT / "BENCH_elastic.json"
    out.write_text(json.dumps(result, indent=2) + "\n")
    assert grows_burst >= 1, "burst never triggered a grow"
    assert shrinks >= 1, "drain never triggered a shrink"
    assert flaps == 0, f"{flaps} resize flap(s): events {''.join(events)}"
    assert cliff >= 0.5, f"throughput cliff {cliff:.2f}x below 0.5x steady"
    if not quiet:
        for name, p in phases.items():
            print(f"elastic/{name:10s} {p['mops']:8.3f} Mops/s")
        print(f"[summary] cliff {cliff:.2f}x, {eng.stats.grows} grow(s) / "
              f"{shrinks} shrink(s), {flaps} flap(s), final load "
              f"{result['final_load']:.3f} @ {final_slots} slots -> {out}")
    return result


if __name__ == "__main__":
    run()                  # uniform keys (the paper's §6.2 setup)
    run(skew=1.2)          # hot-key zipf via the shared skew source
    run_elastic()          # elastic burst round trip (BENCH_elastic.json)
