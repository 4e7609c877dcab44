"""SIVF benchmark on the chip: one run of one cell.

    python3 benchmarks/chip/run.py --workload sift1m.search --seed 7 \
        --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``) and a traffic mix (``traffic/<name>.json``, read
by ``generator.py``). The run makes the rows on the device (from the
configuration's ``dataset_seed`` where it sets one, else from ``--seed``)
and the queries from ``--seed``, trains the coarse quantizer, ingests the
base rows, warms every shape the window uses (all of that is ``setup_s``),
then serves the traffic through ``ServeEngine`` for ``--seconds``. Once the
window has closed it reads the device's memory peak, reads the live ids
back, frees the index and compares a sample of the answers with the plain
reference (``reference.py``).

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the result holds
its per-layer metrics. Each metric is read by ``metrics/<name>.py``: the
end-to-end ones from the window's clock readings and the memory peak, the
per-layer ones from the trace and from the engine's per-request fields.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``); the last lines of standard error are
the numbers compared, each beside its limit. Without a TPU, with fewer
chips than the cell asks for, or without the system's sources beside the
benchmark, it prints no result and exits non-zero.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent
CACHE = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell needs."""


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax():
    """Put the system's sources on the path and point JAX's persistent
    compilation cache at a fixed directory (``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` in the checkout)."""
    if not (ROOT / "src" / "repro").is_dir():
        raise NoChip(f"no SIVF sources under {ROOT / 'src'}: run from a "
                     f"checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def devices_for(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: list, ctx) -> dict:
    """Each metric by its reader; a reader that finds nothing to read
    returns None, and the metric is left out."""
    out = {}
    for m in metrics:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(args, device_check=devices_for, cell=None) -> tuple[dict, list]:
    """One run; returns the result object and the check lines. Tests pass
    their own ``device_check`` and a small ``cell``."""
    jax = setup_jax()
    import harness
    import numpy as np
    import peaks
    cell = cell or harness.load_cell(args.workload)
    devs = device_check(jax, cell.chips)
    peak = peaks.peaks(devs[0].device_kind) if devs[0].platform == "tpu" \
        else None
    dep = harness.Deployment(cell.conf, cell.traffic, args.seed)
    dep.warm()
    setup_s = time.perf_counter() - T_START

    tdir = tempfile.mkdtemp(prefix="sivf-trace-") if args.trace else None

    def on_open():
        if tdir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)

    stopping = []

    def on_close():
        # writing the trace out takes seconds to a minute; the window's
        # clients go on meanwhile
        if tdir:
            th = threading.Thread(target=jax.profiler.stop_trace,
                                  name="bench-stop-trace")
            th.start()
            stopping.append(th)

    try:
        w = harness.serve(dep, cell.qps_unit, args.seconds, args.seed,
                          on_open=on_open, on_close=on_close,
                          trace_s=cell.traffic.trace_seconds)
        for th in stopping:
            th.join()
        used = devs[:cell.chips]
        peak_bytes = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in used)
        e2e = read_metrics(cell.end_to_end, SimpleNamespace(
            window=w, setup_s=setup_s, peak_bytes=peak_bytes,
            live_rows=int(dep.index.n_live), conf=cell.conf))
        live_ids = harness.readback(dep)
        sample = harness.sample_answers(dep, w, args.seed)
        dep.index = None                     # free the program's state
        gc.collect()
        chk = harness.check(dep, w, live_ids, sample, cell.conf["limits"])
        metrics, breakdown, dev_extra = e2e, None, {}
        if args.trace:
            from xplane import Trace
            tr = Trace.load(tdir)
            lo, hi = w.trace_window
            metrics = read_metrics(cell.per_layer, SimpleNamespace(
                trace=tr, window=w, conf=cell.conf, peak=peak,
                answers=[a for a in w.answers if lo <= a.t_done <= hi],
                work=lambda: harness.window_work(dep, w)))
            breakdown = {"device_ops": tr.top_ops(10),
                         "idle_gaps": tr.idle_gaps(10)}
            dev_extra = {"busy_s": tr.busy_s(), "window_s": tr.window_s}
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)

    lat = np.asarray(w.lat_s) * 1e3
    late = np.asarray(w.late_s) * 1e3
    info = {
        "cell": cell.name,
        "rate_qps": cell.qps_unit * cell.traffic.mean_load,
        "seconds": args.seconds,
        "generated": w.attempted, "answered": len(w.lat_s), "shed": w.shed,
        "errors": len(w.errors), "unanswered": w.unanswered,
        "compiles_in_window": w.compiles,
        "generator_late_ms_p50": float(np.median(late)) if late.size else 0.0,
        "generator_late_ms_max": float(late.max()) if late.size else 0.0,
        "search_p50_ms": float(np.median(lat)) if lat.size else None,
        "search_p99_ms": float(np.percentile(lat, 99)) if lat.size else None,
        "mutation_pairs": w.mut_pairs, "mutation_rows": w.mut_rows,
        "setup_s": setup_s, **chk.info,
    }
    if w.errors:
        info["first_error"] = w.errors[0][:500]
    for k, v in info.items():
        print(f"info {k}: {v}", flush=True)
    failed = w.shed + len(w.errors) + w.unanswered
    attempted = w.attempted + 2 * w.mut_pairs
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes, **dev_extra}
    result = {"correct": chk.correct, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in chk.numbers}
    lines = [f"check {n}: {v!r} (limit {lim!r}) "
             f"{'ok' if v <= lim else 'FAILED'}" for n, v, lim in chk.numbers]
    return result, lines


def main(argv=None) -> int:
    args = _args(argv)
    try:
        result, lines = run(args)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
