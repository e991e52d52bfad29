"""The control: the program with one stated guarantee broken, which the
comparison must refuse.

Its lookups compare only the low ``bits`` bits of each key (a key
fingerprint, the narrowing that would tempt a later change: half the key
bytes of every probe window), through the program's own window math and
ordered check, so a lookup can answer with the first slot whose
fingerprint matches, another key's value.  That breaks "exact answers".
Inserts and deletes run through the program's step unchanged.

    python3 -m bench.control --workload <cell> --seconds <s> --seed <n> [<n> ...]

runs the cell once per seed with the control in the program's place and
prints each run's result line; it is not part of the benchmark's runs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial

BITS = 16


def _fp_table(t, keys, bits):
    import jax.numpy as jnp

    from repro.core import hashing
    from repro.kernels import window

    c = t.capacity
    m = jnp.int32((1 << bits) - 1)
    h0 = hashing.bucket_of(t.hfn, keys, c)
    kw, sw = window.windows((t.key, t.state), h0, c, t.max_probes)
    hit, _, _, _ = window.probe(kw & m, sw, (h0 % window.LANES)[:, None],
                                (keys & m)[:, None], t.max_probes)
    hit = hit[:, 0]
    found = hit >= 0
    loc = jnp.where(found, window.slot(h0, hit) % c, 0)
    return found, jnp.where(found, t.val[loc], 0)


def fingerprint_lookup(d, keys, *, bits: int = BITS):
    """``dhash.lookup`` with fingerprint compares: old -> hazard -> new."""
    import jax
    import jax.numpy as jnp

    from repro.core import dhash

    def fast(dd):
        return _fp_table(dd.old, keys, bits)

    def slow(dd):
        fo, vo = _fp_table(dd.old, keys, bits)
        fh, vh = dhash._hazard_probe(dd, keys)
        fn, vn = _fp_table(dd.new, keys, bits)
        return fo | fh | fn, jnp.where(fo, vo, jnp.where(fh, vh, vn))

    return jax.lax.cond(d.rebuilding, slow, fast, d)


class Fingerprint:
    """A service whose lookup answers come from ``fingerprint_lookup`` on
    the state each batch's lookups see (before its inserts and deletes)."""

    def __init__(self, inner, bits: int = BITS):
        import jax
        self.inner = inner
        self.lookup = jax.jit(partial(fingerprint_lookup, bits=bits))

    def submit(self, b):
        import jax.numpy as jnp
        fv = self.lookup(self.inner.engine.state, jnp.asarray(b.look))
        return fv, self.inner.submit(b)

    def fetch(self, handle):
        import jax
        fv, h = handle
        ans = self.inner.fetch(h)
        ans.found, ans.vals = jax.device_get(fv)
        return ans

    def live(self) -> int:
        return self.inner.live()


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import cells, harness, run
    cell = cells.load(args.workload)
    devices = jax.devices()[: cell.chips]
    if devices[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 1
    run.prepare(jax)
    compiles = harness.Compiles()
    for seed in args.seed:
        out = harness.run(cell, seed, args.seconds, False, t_start=t_start,
                          devices=devices, compiles=compiles,
                          wrap=Fingerprint)
        print(json.dumps({"control": "fingerprint", "bits": BITS,
                          "seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
