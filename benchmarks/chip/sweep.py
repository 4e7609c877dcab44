"""Find the highest search rate a configuration sustains, on the chip.

    python3 benchmarks/chip/sweep.py --workload sift1m.search --seed 3 \
        --seconds 16 --rates 140 150 155 160 165

One set-up, then one open-loop window per mean rate (``--rates``, in
searches per second), through the same served path and traffic mix as the
cell (its writer and its burst profile included). A rate is sustained when
the window completes at least ``MIN_DONE`` of what it offered and the mean
latency of its last fifth of requests is at most ``MAX_GROWTH`` times that
of its first fifth: a queue that grows through the window fails the second
test before the first. Prints one JSON line per rate, then the highest rate
sustained below the first that is not. That rate is fixed in the
configuration (``sustained_qps``) and the mix's ``load`` scales it; the
benchmark never searches for one.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench

MIN_DONE = 0.97
MAX_GROWTH = 1.05


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=16.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    jax = bench.setup_jax()
    import harness
    import numpy as np
    cell = harness.load_cell(args.workload)
    bench.devices_for(jax, cell.chips)
    dep = harness.Deployment(cell.conf, cell.traffic, args.seed)
    dep.warm()
    best = None
    for rate in sorted(args.rates):
        w = harness.serve(dep, rate / cell.traffic.mean_load, args.seconds,
                          args.seed)
        lat = np.asarray(w.lat_s) * 1e3
        # completions per second after the first second, against the rate
        lo, hi = w.t0 + 1.0, w.t0 + args.seconds
        done = sum(lo <= a.t_done <= hi for a in w.answers) / (hi - lo)
        fifth = max(1, len(lat) // 5)
        first, last = float(np.mean(lat[:fifth])), float(np.mean(lat[-fifth:]))
        ok = done >= MIN_DONE * rate and last <= MAX_GROWTH * first
        print(json.dumps({
            "rate": rate, "offered": w.attempted, "throughput_qps": done,
            "p50_ms": float(np.median(lat)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_fifth_ms": first, "last_fifth_ms": last,
            "sustained": bool(ok), "shed": w.shed,
            "mutation_pairs": w.mut_pairs,
            "late_ms_max": float(np.max(w.late_s)) * 1e3}), flush=True)
        if not ok:
            break
        best = rate
    print(json.dumps({"sustained_qps": best}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
