import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first init). Everything below is ordinary code.
if os.environ.get("REPRO_DRYRUN_DEVICES"):      # test override (smaller mesh)
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent on the
production mesh (sharding propagates, memory fits, collectives lower) and
extracts the §Roofline inputs: cost_analysis FLOPs/bytes, memory_analysis,
and the collective schedule parsed from post-SPMD HLO.

Results are cached incrementally in a JSON file so the sweep is resumable.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cell_runnable
from repro.launch import roofline as R
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh, mesh_axes_dict
from repro.models import model as M
from repro.sharding import axes as AX
from repro.sharding.rules import make_plan
from repro.train.train_step import (TrainConfig, init_train_state,
                                    make_train_step, state_specs)


def _to_dtype(tree, dtype):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype),
        tree)


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = ARCHS[arch_name]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    maxes = mesh_axes_dict(mesh)
    plan = make_plan(cfg, maxes, shape_kind=shape.kind,
                     global_batch=shape.global_batch)
    rules = plan.rules_dict
    chips = mesh.devices.size

    max_seq = shape.seq_len
    params_annot = SP.abstract_params(cfg, plan, max_seq=max_seq)
    params_sh = SP.param_shardings(params_annot, mesh, rules)
    params_abs = AX.strip(params_annot)
    batch_abs = SP.input_specs(cfg, shape)
    batch_sh = SP.input_shardings(cfg, shape, plan, mesh)

    t0 = time.time()
    with jax.set_mesh(mesh), AX.use_rules(rules):
        if shape.kind == "train":
            tcfg = TrainConfig()
            step_fn = make_train_step(cfg, plan, tcfg)
            state_abs = jax.eval_shape(init_train_state, params_abs)
            state_sh = state_specs(
                params_sh, params_abs=params_abs,
                batch_axes=plan.batch_axes, mesh_axes=maxes,
                zero1=os.environ.get("REPRO_ZERO1", "1") == "1")
            state_sh["opt"]["step"] = NamedSharding(mesh, P())
            fn = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                         donate_argnums=(0,))
            lowered = fn.lower(state_abs, batch_abs)
        elif shape.kind == "prefill":
            params_serve = _to_dtype(params_abs, jnp.dtype(cfg.dtype))

            def prefill(params, batch):
                logits, _, _ = M.forward(params, cfg, plan, batch)
                return logits

            fn = jax.jit(prefill, in_shardings=(params_sh, batch_sh))
            lowered = fn.lower(params_serve, batch_abs)
        else:  # decode
            params_serve = _to_dtype(params_abs, jnp.dtype(cfg.dtype))
            cache_abs = SP.abstract_decode_cache(
                cfg, plan, shape.global_batch, max_seq)
            cache_sh = SP.cache_shardings(cfg, plan, cache_abs, mesh)

            def serve_step(params, tokens, caches, pos):
                logits, new_caches = M.decode_step(
                    params, cfg, plan, tokens, caches, pos)
                return logits, new_caches

            fn = jax.jit(
                serve_step,
                in_shardings=(params_sh, batch_sh["tokens"], cache_sh,
                              NamedSharding(mesh, P())),
                donate_argnums=(2,))
            lowered = fn.lower(params_serve, batch_abs["tokens"], cache_abs,
                               jax.ShapeDtypeStruct((), jnp.int32))
        lower_s = time.time() - t0

        t1 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t1

    # -- extract roofline inputs --------------------------------------------
    # XLA cost_analysis counts while bodies ONCE (verified; see the HLO
    # analyzer docstring) — kept only as a cross-check column. The
    # trip-count-aware analyzer provides the real per-device numbers.
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):     # old jaxlib: list of per-program dicts
        cost = cost[0] if cost else {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        }
    except Exception:
        mem_info = {}
    from repro.launch.hlo_analyzer import analyze
    hlo = analyze(compiled.as_text())
    # analyzer numbers are per-device (post-SPMD shapes): totals x chips
    flops = hlo["flops"] * chips
    bytes_acc = hlo["memory_bytes"] * chips
    wire = hlo["collective_wire_bytes"] * chips
    terms = R.roofline_terms(flops, bytes_acc, wire, chips)
    mflops = R.model_flops(cfg, shape)

    return {
        "status": "ok",
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "chips": chips,
        "plan": {
            "n_heads_padded": plan.n_heads_padded,
            "n_kv_heads_padded": plan.n_kv_heads_padded,
            "kv_sharded": plan.kv_sharded,
            "vocab_padded": plan.vocab_padded,
            "n_experts_padded": plan.n_experts_padded,
        },
        "lower_s": round(lower_s, 2),
        "compile_s": round(compile_s, 2),
        "hlo_flops": flops,
        "hlo_bytes": bytes_acc,
        "xla_cost_analysis_flops": xla_flops,   # cross-check (body-once)
        "xla_cost_analysis_bytes": xla_bytes,
        "model_flops": mflops,
        "useful_flops_frac": mflops / flops if flops else None,
        "memory": mem_info,
        "collectives": {
            "per_device": hlo["collectives"],
            "wire_bytes_total": wire,
        },
        "roofline": terms,
    }


def cell_key(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}|{shape}|{'multi' if multi_pod else 'single'}"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun_results.json")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    for arch in archs:
        for shape in shapes:
            runnable, reason = cell_runnable(ARCHS[arch], SHAPES[shape])
            for mp in meshes:
                key = cell_key(arch, shape, mp)
                if key in results and results[key].get("status") == "ok" \
                        and not args.force:
                    print(f"[skip-cached] {key}")
                    continue
                if not runnable:
                    results[key] = {"status": "skipped", "arch": arch,
                                    "shape": shape, "reason": reason}
                    print(f"[skip] {key}: {reason}")
                else:
                    print(f"[lower+compile] {key} ...", flush=True)
                    try:
                        results[key] = lower_cell(arch, shape, mp)
                        r = results[key]
                        print(f"  ok: compile={r['compile_s']}s "
                              f"flops={r['hlo_flops']:.3e} "
                              f"dominant={r['roofline']['dominant']}",
                              flush=True)
                    except Exception as e:
                        results[key] = {
                            "status": "error", "arch": arch, "shape": shape,
                            "error": f"{type(e).__name__}: {e}",
                            "traceback": traceback.format_exc()[-2000:],
                        }
                        print(f"  ERROR: {e}", flush=True)
                out_path.write_text(json.dumps(results, indent=1))

    n_ok = sum(1 for v in results.values() if v.get("status") == "ok")
    n_skip = sum(1 for v in results.values() if v.get("status") == "skipped")
    n_err = sum(1 for v in results.values() if v.get("status") == "error")
    print(f"\ndone: {n_ok} ok, {n_skip} skipped (documented), {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
