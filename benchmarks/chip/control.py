"""Readings that set the limits of the check, on the chip, at a cell's size.

    python3 benchmarks/chip/control.py --workload sift1m.search \
        --seconds 5 --seeds 11 12 13

For each seed, in one process: the cell's set-up and a short window at the
cell's own load, exactly as ``run.py`` makes them. Then, on the same sampled
answers, two readings of the numbers that decide ``correct``:

* ``program``: the answers the served path gave (a sound run);
* ``control``: the plain reference put in the program's place and computed
  one precision below the configuration's float32 (operands rounded to
  bfloat16, as a TPU's default matmul does), for the same queries at the
  same epochs.

The lower reading of a limit is the largest ``program`` value over a dozen
seeds or more, its upper reading the smallest ``control`` value; ``PERF.md``
records both. Prints one JSON line per seed. The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as bench


def control_reading(dep, sample, k: int, nprobe: int) -> dict:
    """The reference at bfloat16 in the program's place, compared like the
    program's answers."""
    import jax.numpy as jnp

    import reference
    q = jnp.asarray(sample["queries"])
    dist, labels = reference.search(dep.mix, dep.centroids, dep.ledger, q,
                                    sample["epoch"], k, nprobe,
                                    precision="bf16")
    res = reference.compare(dep.mix, dep.centroids, dep.ledger, q,
                            sample["epoch"], labels, dist, k, nprobe)
    return {"gap": res["gap"], "stray": res["stray"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    jax = bench.setup_jax()
    import harness
    cell = harness.load_cell(args.workload)
    bench.devices_for(jax, cell.chips)
    for seed in args.seeds:
        dep = harness.Deployment(cell.conf, cell.traffic, seed)
        dep.warm()
        w = harness.serve(dep, cell.qps_unit, args.seconds, seed)
        live = harness.readback(dep)
        sample = harness.sample_answers(dep, w, seed)
        dep.index = None
        gc.collect()
        chk = harness.check(dep, w, live, sample, cell.conf["limits"])
        ctl = control_reading(dep, sample, dep.k, dep.nprobe)
        print(json.dumps({"cell": cell.name, "seed": seed,
                          "answered": len(w.answers),
                          "program": {n: v for n, v, _ in chk.numbers},
                          "control": ctl, **chk.info}), flush=True)
        del dep, w, sample
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
