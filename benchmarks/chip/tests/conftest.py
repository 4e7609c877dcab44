"""Tests of the benchmark's own code, run on the CPU by explicit path:

    python3 -m pytest -q benchmarks/chip/tests
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent.parent
for p in (ROOT / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402


def tiny_cell(writer: bool, traffic_over=None, **conf_over):
    """The sift1m configuration and a cell's traffic at a size the CPU
    runs in seconds; every other setting as the chip runs it.
    ``traffic_over`` replaces top-level keys of the mix's file, a
    ``generator`` in ``conf_over`` keys of the configuration's generator."""
    import harness
    from generator import Mix
    conf = json.loads((BENCH / "configs" / "sift1m.json").read_text())
    conf.update(dim=16, base_rows=6000, n_lists=16, nprobe=4, n_slabs=1024,
                max_chain=64, n_max=16384, sustained_qps=100)
    conf["generator"].update(batch_rows=1024, kmeans_sample=2048,
                             **conf_over.pop("generator", {}))
    conf.update(conf_over)
    traffic = json.loads((BENCH / "traffic" / (
        "churn_window.json" if writer else "search_open_loop.json")).read_text())
    traffic.update(query_pool=2048)
    if writer:
        traffic["searches"]["profile"] = [{"seconds": 1, "load": 0.8}]
        traffic.update(writer={"tenant": "ingest", "batches": [1024]},
                       trace_seconds=1.0)
    traffic.update(traffic_over or {})
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = "sift1m.churn" if writer else "sift1m.search"
    e2e = [m for m in bench["end_to_end"] if harness._applies(m, name)]
    layer = [m for m in bench["per_layer"] if name in m.get("workloads", ())]
    return harness.Cell(name, conf, Mix.from_json(traffic, 1024), e2e, layer,
                        1)


@pytest.fixture
def run_cell():
    """Drive a whole run of a tiny cell past the look for a chip."""
    import run as bench
    from types import SimpleNamespace

    def go(writer=False, seed=2 ** 31 + 99, seconds=1.5, trace=0,
           traffic_over=None, **over):
        bench.setup_jax()
        import harness
        harness.SAMPLE = 64
        args = SimpleNamespace(workload="tiny", seed=seed, seconds=seconds,
                               trace=trace)
        return bench.run(args, device_check=lambda jax, chips: jax.devices(),
                         cell=tiny_cell(writer, traffic_over, **over))
    return go
