"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes
experiments/bench_results.json. Run: PYTHONPATH=src python -m benchmarks.run
[names ...] [--only fig1a,...] [--skip-dist] [--deferred]

``streaming_churn --deferred`` runs the eager AND deferred churn variants
back-to-back and records p50/p99 latencies + jit compile counts to
``BENCH_streaming_churn.json``; ``pq_sweep`` always records its summary
(QPS, recall@10, measured slab temp bytes at Q=16/64/256) to
``BENCH_pq.json``; ``reshard_sweep`` records elastic-reshard wall-clock +
bytes moved for 1->2->4 shards at 100k vectors (PQ on/off, search-parity
asserted) to ``BENCH_reshard.json``; ``filtered_sweep`` records filtered-search QPS +
recall@10 at ~1%/10%/50% predicate selectivity vs the post-filter-then-
widen baseline (plus the jit executable count across filter structures)
to ``BENCH_filter.json``; ``serve_churn`` records the
open-loop mixed-workload SLO sweep (p50/p99/p999 search latency idle vs
under ingest at 3 arrival rates + sustained mutation throughput) to
``BENCH_serve.json`` (plus ``TELEMETRY_serve.json``, the engine's
end-of-run telemetry snapshot, uploaded as a CI artifact);
``tiered_sweep`` records the host-tier/device-
cache sweep (hit rate + QPS at working sets of 0.25x-2x the device slab
budget, bit-parity asserted against the all-resident pool) to
``BENCH_tiered.json``; ``obs_overhead`` records the telemetry-on vs
telemetry-off serve p99 comparison (median paired ratio gated at 1.05x
in-bench) to ``BENCH_obs.json``; ``drift_sweep`` records recall@10 under
a 12-step cluster-drift schedule for a maintained index (online
split/merge/recluster each step) vs a frozen-centroid twin on the
identical stream (maintained >= 0.95 and frozen decay both asserted
in-bench) to ``BENCH_drift.json`` (the slow CI job's perf data points —
``scripts/check_bench.py`` gates them against committed baselines).

Exceptions inside one benchmark print a ``<name>.ERROR`` row and the run
continues, so a multi-artifact sweep survives a single failure; the
harness then exits non-zero at the end if *any* artifact errored, so a
swallowed exception never passes as a result.

If ``JAX_COMPILATION_CACHE_DIR`` is unset, compiled programs are cached in
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

from benchmarks import paper, serve_bench

ARTIFACTS = [
    ("fig1a", paper.fig1a_physical_deletion_overhead),
    ("fig1b", paper.fig1b_tombstone_compaction_trap),
    ("fig2", paper.fig2_ingestion_micro),
    ("fig3", paper.fig3_deletion_micro),
    ("fig4_5", paper.fig4_5_parameter_sensitivity),
    ("fig6_7_8", paper.fig6_7_8_real_datasets),
    ("fig9", paper.fig9_recall_pareto),
    ("fused", paper.fused_search_sweep),
    # pq_sweep is dispatched by name in main() (its summary-writing variant
    # records BENCH_pq.json), not through this table
    ("streaming_churn", paper.streaming_churn),
    ("streaming_churn_deferred", paper.streaming_churn_deferred),
    ("fig10", paper.fig10_zipfian_skew),
    ("fig11", paper.fig11_sliding_window),
    ("tab1", paper.tab1_tail_latency),
    ("tab2", paper.tab2_mixed_workload),
    ("tab3", paper.tab3_time_breakdown),
    ("tab4", paper.tab4_non_ivf_indexes),
]


def run_summary_artifact(name: str, fn, bench_path: str, results: dict
                         ) -> None:
    """Run a (rows, summary) benchmark, print rows, record results, and
    write the summary JSON next to the repo root (the slow CI job uploads
    it). Errors are recorded like the generic loop and fail the run at
    its end."""
    t0 = time.time()
    try:
        rows, summary = fn()
        for r in rows:
            print(r.csv(), flush=True)
        results[name] = [
            {"name": r.name, "us": r.us, "derived": r.derived}
            for r in rows]
        bench_out = Path(bench_path)
        bench_out.write_text(json.dumps(summary, indent=1))
        print(f"# wrote {bench_out}")
    except Exception as e:  # keep the harness going
        print(f"{name}.ERROR,0,{type(e).__name__}: {e}", flush=True)
        results[name] = {"error": traceback.format_exc()[-1500:]}
    results.setdefault("_timing", {})[name] = round(time.time() - t0, 1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("names", nargs="*", default=[],
                    help="artifact names to run (same as --only)")
    ap.add_argument("--only", default="")
    ap.add_argument("--skip-dist", action="store_true")
    ap.add_argument("--deferred", action="store_true",
                    help="run streaming_churn in eager+deferred comparison "
                         "mode and write BENCH_streaming_churn.json")
    args = ap.parse_args()
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(__file__).resolve().parent.parent
                              / ".jax_cache"))
    only = set(args.only.split(",")) if args.only else set()
    only |= set(args.names)
    only = only or None

    print("name,us_per_call,derived")
    results = {}
    artifacts = list(ARTIFACTS)
    if args.deferred and (only is None or "streaming_churn" in only):
        artifacts = [(n, f) for n, f in artifacts
                     if n not in ("streaming_churn",
                                  "streaming_churn_deferred")]
        run_summary_artifact("streaming_churn", paper.streaming_churn_compare,
                             "BENCH_streaming_churn.json", results)
    if only is None or "pq_sweep" in only:
        # pq_sweep always runs through its summary variant so the slab-DMA /
        # recall data point lands in BENCH_pq.json next to the churn artifact
        run_summary_artifact("pq_sweep", paper.pq_sweep_summary,
                             "BENCH_pq.json", results)
    if only is None or "reshard_sweep" in only:
        run_summary_artifact("reshard_sweep", paper.reshard_sweep_summary,
                             "BENCH_reshard.json", results)
    if only is None or "filtered_sweep" in only:
        run_summary_artifact("filtered_sweep", paper.filtered_sweep_summary,
                             "BENCH_filter.json", results)
    if only is None or "serve_churn" in only:
        run_summary_artifact("serve_churn", serve_bench.serve_churn_summary,
                             "BENCH_serve.json", results)
    if only is None or "tiered_sweep" in only:
        from benchmarks import tiered_bench
        run_summary_artifact("tiered_sweep",
                             tiered_bench.tiered_sweep_summary,
                             "BENCH_tiered.json", results)
    if only is None or "obs_overhead" in only:
        from benchmarks import obs_bench
        run_summary_artifact("obs_overhead",
                             obs_bench.obs_overhead_summary,
                             "BENCH_obs.json", results)
    if only is None or "drift_sweep" in only:
        from benchmarks import drift_bench
        run_summary_artifact("drift_sweep",
                             drift_bench.drift_sweep_summary,
                             "BENCH_drift.json", results)
    for name, fn in artifacts:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn()
            for r in rows:
                print(r.csv(), flush=True)
            results[name] = [
                {"name": r.name, "us": r.us, "derived": r.derived}
                for r in rows]
        except Exception as e:  # keep the harness going
            print(f"{name}.ERROR,0,{type(e).__name__}: {e}", flush=True)
            results[name] = {"error": traceback.format_exc()[-1500:]}
        results.setdefault("_timing", {})[name] = round(time.time() - t0, 1)

    if not args.skip_dist and (only is None or "fig13" in only):
        try:
            from benchmarks import distributed_bench
            scale = distributed_bench.run(dim=64)
            base = scale[0]
            for row in scale:
                s = row["shards"]
                print(f"fig13.scaling@shards={s},0,"
                      f"ingest={row['ingest_vps']:.0f}vps "
                      f"search={row['search_qps']:.0f}qps "
                      f"delete={row['delete_vps']:.0f}vps "
                      f"ingest_speedup={row['ingest_vps'] / base['ingest_vps']:.2f}x",
                      flush=True)
            results["fig13"] = scale
            # fig14: higher-dim (DINO-like) distributed comparison
            scale14 = distributed_bench.run(dim=256)
            for row in scale14:
                print(f"fig14.dino_like@shards={row['shards']},0,"
                      f"ingest={row['ingest_vps']:.0f}vps "
                      f"delete={row['delete_vps']:.0f}vps", flush=True)
            results["fig14"] = scale14
        except Exception as e:
            print(f"fig13.ERROR,0,{type(e).__name__}: {e}", flush=True)
            results["fig13"] = {"error": traceback.format_exc()[-1500:]}

    out = Path("experiments/bench_results.json")
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(results, indent=1))
    print(f"# wrote {out}")
    errored = sorted(name for name, v in results.items()
                     if isinstance(v, dict) and "error" in v)
    if errored:
        print(f"# errored artifacts: {','.join(errored)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
