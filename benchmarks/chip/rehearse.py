"""Rehearse the cells without a chip: memory of every executable, and list
fill of every configuration's base set.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \
        [config ...] [--seeds N ...]

A config is a name under ``configs/`` or the path of a JSON file of the
same form (a configuration being sized before it is committed).

1. Compiles, for a described TPU v5e, every executable a cell runs: the
   index's insert and delete at the ingest batch, its search at each query
   bucket up to the engine's tile cap, and the benchmark's own generator, k-means and reference block.
   Prints each one's ``memory_analysis()`` (arguments, outputs, aliased,
   temporaries) and the state's bytes. Nothing runs; a compile that passes
   is not a chip run.
2. On the CPU, makes each configuration's base rows from a seed, trains the
   benchmark's k-means and routes every row, and prints the fullest list
   against ``max_chain * capacity`` (the bound at which an insert stops with
   ``CHAIN_OVERFLOW``), once per seed. The generator is the configuration's
   own (``datagen.Mixture.of``): its ``std`` and, where set, ``noise_rank``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent.parent


def described_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"args": m.argument_size_in_bytes, "out": m.output_size_in_bytes,
            "alias": m.alias_size_in_bytes, "temp": m.temp_size_in_bytes}


def compile_all(conf: dict, sh) -> dict:
    import jax
    import jax.numpy as jnp

    import datagen
    import reference
    from harness import engine_cfg
    from repro.core.api import _single_ops
    from repro.core.state import init_state

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    cfg = engine_cfg(conf)
    d, b = conf["dim"], conf["generator"]["batch_rows"]
    cents = spec((conf["n_lists"], d), jnp.float32)
    state = jax.eval_shape(lambda c: init_state(cfg, c), cents)
    state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
    ops = _single_ops(cfg, "pallas", 8, None)
    out = {"state_bytes": sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(state))}
    ids = spec((b,), jnp.int32)
    attrs = spec((b, len(conf["attributes"])), jnp.int32)
    out["insert"] = mem(ops.insert.lower(state, spec((b, d), jnp.float32),
                                         ids, attrs).compile())
    out["delete"] = mem(ops.delete.lower(state, ids).compile())
    eng = conf["engine"]
    buckets = [eng["min_bucket"]]
    while buckets[-1] < eng["max_coalesce"]:
        buckets.append(2 * buckets[-1])
    for q in buckets:
        c = ops.search.lower(state, spec((q, d), jnp.float32), conf["k"],
                             conf["nprobe"], None, None).compile()
        assert "tpu_custom_call" in c.as_text()
        out[f"search{q}"] = mem(c)
    key = jax.eval_shape(lambda: datagen.seed_key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=sh)
    gen = conf["generator"]
    gc = spec((gen["components"], d), jnp.float32)
    std = float(gen["std"])
    basis = spec((gen["noise_rank"], d), jnp.float32) \
        if "noise_rank" in gen else None
    out["generator"] = mem(datagen._take.lower(
        key, gc, spec((), jnp.int32), spec((), jnp.int32), rows=b, n=b,
        stream=0, std=std, basis=basis).compile())
    out["kmeans"] = mem(reference.kmeans.lower(
        key, spec((conf["generator"]["kmeans_sample"], d), jnp.float32),
        n_lists=conf["n_lists"], iters=conf["generator"]["kmeans_iters"]
    ).compile())
    s, k, kk, nb = 512, conf["k"], conf["k"] + reference.EXTRA, 4
    carry = jax.tree.map(lambda x: spec(x.shape, x.dtype), jax.eval_shape(
        lambda: reference._init(s, k, kk, d)))
    lmask = spec((s, conf["n_lists"]), jnp.bool_)
    ep = spec((nb * b,), jnp.int32)
    out["reference_block"] = mem(reference._pass_block.lower(
        carry, key, gc, std, basis, cents, spec((s, d), jnp.float32), lmask,
        lmask, spec((), jnp.int32), ep, ep, spec((s,), jnp.int32),
        spec((s, k), jnp.int32), rows=b, n_gen=nb, kk=kk,
        precision="f32").compile())
    return out


def list_fill(conf: dict, seed: int) -> dict:
    import numpy as np

    import datagen
    import reference
    gen = conf["generator"]
    mix = datagen.Mixture.of(conf, seed)
    import jax
    import jax.numpy as jnp
    train = jnp.concatenate([mix.batch(i) for i in range(
        -(-gen["kmeans_sample"] // mix.rows))])[:gen["kmeans_sample"]]
    cents = reference.kmeans(jax.random.fold_in(mix.key, 1), train,
                             conf["n_lists"], gen["kmeans_iters"])
    live = np.ones(conf["base_rows"], bool)
    counts = reference.list_rows(mix, cents, live)
    bound = conf["max_chain"] * conf["capacity"]
    return {"seed": seed, "rows": int(counts.sum()),
            "fullest": int(counts.max()), "bound": bound,
            "slabs_needed": int(np.sum(-(-counts // conf["capacity"]))),
            "n_slabs": conf["n_slabs"]}


def load_conf(name: str) -> dict:
    path = Path(name) if name.endswith(".json") else \
        HERE / "configs" / f"{name}.json"
    return json.loads(path.read_text())


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*")
    ap.add_argument("--seeds", type=int, nargs="+", default=[2 ** 31 + 11])
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    names = args.configs or [p.stem for p in
                             sorted((HERE / "configs").glob("*.json"))]
    confs = {name: load_conf(name) for name in names}
    sh = described_chip()
    for name, conf in confs.items():
        t = time.perf_counter()
        print(name, "compiled:", json.dumps(compile_all(conf, sh)),
              f"({time.perf_counter() - t:.1f} s)", flush=True)
    import jax
    jax.config.update("jax_platforms", "cpu")
    for name, conf in confs.items():
        for seed in args.seeds:
            t = time.perf_counter()
            print(name, "list fill:", json.dumps(list_fill(conf, seed)),
                  f"({time.perf_counter() - t:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
