"""MLPs: SwiGLU / GELU dense blocks and sort-based Mixture-of-Experts.

The MoE dispatch is the TPU-idiomatic sort+capacity plan (no [T,E,cap]
one-hot tensors): tokens are sorted by expert, ranked within expert by the
same segmented-prefix-sum machinery the SIVF core uses for slab slot
assignment (repro.core.index), gathered into an [E, cap, d] buffer, run
through batched expert einsums, and scattered back weighted. Experts shard
over the model axis (expert parallelism); the capacity dim shards over the
data axes so dispatch collectives stay in the all-to-all family.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense, dense_init
from repro.sharding.axes import constrain
from repro.sharding.rules import ShardPlan
from repro.utils import round_up


# -- dense MLP ---------------------------------------------------------------

def init_mlp(key, d: int, d_ff: int, act: str) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[0], d, d_ff, "embed", "mlp"),
        "w_down": dense_init(ks[1], d_ff, d, "mlp", "embed"),
    }
    if act == "swiglu":
        p["w_gate"] = dense_init(ks[2], d, d_ff, "embed", "mlp")
    return p


def apply_mlp(p, x, act: str):
    if act == "swiglu":
        h = jax.nn.silu(dense(p["w_gate"], x)) * dense(p["w_up"], x)
    else:
        h = jax.nn.gelu(dense(p["w_up"], x))
    h = constrain(h, "batch", "seq", "mlp")
    return constrain(dense(p["w_down"], h), "batch", "seq_sp", None)


# -- Mixture of Experts --------------------------------------------------------

def init_moe(key, cfg: ModelConfig, plan: ShardPlan) -> dict:
    d, h = cfg.d_model, cfg.moe_d_ff
    e = plan.n_experts_padded or cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = (1.0 / d) ** 0.5

    def ew(k, shape, ax):
        from repro.sharding.axes import annot
        return annot(jax.random.normal(k, shape, jnp.float32) * scale, *ax)

    # expert weights shard on the expert dim only (expert parallelism over
    # the model axis); the per-expert ffn dim stays local to its shard
    p = {
        "router": dense_init(ks[0], d, e, "embed", "expert"),
        "w_gate": ew(ks[1], (e, d, h), ("expert", None, None)),
        "w_up": ew(ks[2], (e, d, h), ("expert", None, None)),
        "w_down": ew(ks[3], (e, h, d), ("expert", None, None)),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d,
                               cfg.n_shared_experts * cfg.moe_d_ff, "swiglu")
    return p


def apply_moe_shardmap(p, cfg: ModelConfig, plan: ShardPlan, x):
    """Expert-parallel MoE with explicit all-to-all (beyond-paper §Perf).

    The GSPMD-auto dispatch (apply_moe) partitions the global
    token->expert scatter by replicate-then-partition, which costs TBs of
    all-reduce per step at 256 chips (EXPERIMENTS.md §Perf iteration 1).
    This variant runs dispatch *manually* per device inside shard_map:
    local top-k -> local capacity buffers -> one all-to-all over the model
    axis to the expert owners -> expert einsums -> reverse all-to-all ->
    local weighted combine. The only cross-device traffic is the routed
    token payload itself, twice.
    """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.sharding.axes import spec_for
    b, s, d = x.shape
    e_pad = plan.n_experts_padded or cfg.n_experts
    e_real = cfg.n_experts
    k = cfg.moe_top_k
    rules = plan.rules_dict
    m = plan.model_size
    if rules is None or rules.get("seq_sp") != "model" or s % m != 0:
        return apply_moe(p, cfg, plan, x)   # decode / test fallback
    mesh = jax.sharding.get_abstract_mesh()
    dp_total = 1
    for a in plan.batch_axes:
        dp_total *= mesh.shape[a]
    if b % dp_total != 0:
        return apply_moe(p, cfg, plan, x)
    e_loc = e_pad // m
    n_loc = (b // dp_total) * (s // m)
    cap = int(round_up(max(int(n_loc * k * cfg.capacity_factor) // e_real,
                           1), 8))

    def local(xl, router, wg, wu, wd):
        # xl [B_loc, S_loc, d]; router [d, E]; wg/wu [E_loc, d, h]; wd [E_loc, h, d]
        nl = xl.shape[0] * xl.shape[1]
        xf = xl.reshape(nl, d)
        logits = (xf @ router.astype(xl.dtype)).astype(jnp.float32)
        if e_pad != e_real:
            logits = jnp.where(jnp.arange(e_pad) < e_real, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        topw, tope = jax.lax.top_k(probs, k)
        topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)

        load = jnp.zeros((e_pad,), jnp.float32).at[tope.reshape(-1)].add(1.0)
        load = load / (nl * k)
        aux = e_real * jnp.sum(load * jnp.mean(probs, axis=0))
        aux = jax.lax.pmean(aux, "model")

        ek = tope.reshape(nl * k)
        wk_ = topw.reshape(nl * k)
        tok = jnp.arange(nl * k) // k
        order = jnp.argsort(ek, stable=True)
        se, stok, sw = ek[order], tok[order], wk_[order]
        rank = jnp.arange(nl * k) - jnp.searchsorted(se, se, side="left")
        ok = rank < cap
        buf = jnp.zeros((e_pad, cap, d), xl.dtype)
        buf = buf.at[jnp.where(ok, se, e_pad), rank].set(xf[stok],
                                                         mode="drop")
        # ship expert blocks to their owner shard:
        #   [E_pad, cap, d] -> [E_loc, m*cap, d] (sources along dim 1)
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)
        hh = jax.nn.silu(jnp.einsum("ecd,edh->ech", buf, wg.astype(xl.dtype))
                         ) * jnp.einsum("ecd,edh->ech", buf,
                                        wu.astype(xl.dtype))
        outb = jnp.einsum("ech,ehd->ecd", hh, wd.astype(xl.dtype))
        # send results home: [E_loc, m*cap, d] -> [E_pad, cap, d]
        outb = jax.lax.all_to_all(outb, "model", split_axis=1,
                                  concat_axis=0, tiled=True)
        vals = outb[jnp.clip(se, 0, e_pad - 1), jnp.clip(rank, 0, cap - 1)]
        y = jnp.zeros((nl, d), xl.dtype)
        y = y.at[jnp.where(ok, stok, nl)].add(
            vals * sw[:, None].astype(xl.dtype), mode="drop")
        return y.reshape(xl.shape), aux[None]

    x_spec = spec_for(("batch", "seq_sp", None), rules)
    router_spec = P()   # router weight replicated inside the region
    w_spec = spec_for(("expert", None, None), rules)
    y, aux = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(x_spec, router_spec, w_spec, w_spec, w_spec),
        out_specs=(x_spec, P(plan.batch_axes + ("model",))),
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    out = y
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, "swiglu")
    return constrain(out, "batch", "seq_sp", None), jnp.mean(aux)


MOE_IMPL = "shardmap"   # "shardmap" (beyond-paper §Perf) | "gspmd" (baseline)


def moe(p, cfg: ModelConfig, plan: ShardPlan, x):
    """MoE dispatcher; EXPERIMENTS.md §Perf compares the two paths."""
    if MOE_IMPL == "shardmap":
        return apply_moe_shardmap(p, cfg, plan, x)
    return apply_moe(p, cfg, plan, x)


def apply_moe(p, cfg: ModelConfig, plan: ShardPlan, x):
    """x [B,S,d] -> (out [B,S,d], aux_loss scalar)."""
    b, s, d = x.shape
    n = b * s
    e_pad = plan.n_experts_padded or cfg.n_experts
    e_real = cfg.n_experts
    k = cfg.moe_top_k
    xf = x.reshape(n, d)

    logits = dense(p["router"], xf).astype(jnp.float32)       # [N, E]
    if e_pad != e_real:
        logits = jnp.where(jnp.arange(e_pad) < e_real, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, k)                      # [N, K]
    topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style), over real experts
    load = jnp.zeros((e_pad,), jnp.float32).at[tope.reshape(-1)].add(1.0)
    load = load / (n * k)
    imp = jnp.mean(probs, axis=0)
    aux = e_real * jnp.sum(load * imp)

    # sort-based dispatch (same plan machinery as SIVF slab assignment)
    cap = int(round_up(max(int(n * k * cfg.capacity_factor) // e_real, 1), 8))
    ek = tope.reshape(n * k)
    wk = topw.reshape(n * k)
    tok = jnp.arange(n * k) // k
    order = jnp.argsort(ek, stable=True)
    se, stok, sw = ek[order], tok[order], wk[order]
    first = jnp.searchsorted(se, se, side="left")
    rank = jnp.arange(n * k) - first
    ok = rank < cap                                            # capacity drop
    buf = jnp.zeros((e_pad, cap, d), x.dtype)
    buf = buf.at[jnp.where(ok, se, e_pad), rank].set(xf[stok], mode="drop")
    buf = constrain(buf, "expert", "dispatch", None)

    hsh = jnp.einsum("ecd,edh->ech", buf, p["w_gate"].astype(x.dtype))
    hup = jnp.einsum("ecd,edh->ech", buf, p["w_up"].astype(x.dtype))
    hh = jax.nn.silu(hsh) * hup   # [E, cap, moe_d_ff]: E already on model
    hh = constrain(hh, "expert", "dispatch", None)
    out_buf = jnp.einsum("ech,ehd->ecd", hh, p["w_down"].astype(x.dtype))
    out_buf = constrain(out_buf, "expert", "dispatch", None)

    vals = out_buf[jnp.clip(se, 0, e_pad - 1), jnp.clip(rank, 0, cap - 1)]
    y = jnp.zeros((n, d), x.dtype)
    y = y.at[jnp.where(ok, stok, n)].add(
        vals * sw[:, None].astype(x.dtype), mode="drop")

    out = y.reshape(b, s, d)
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, "swiglu")
    return constrain(out, "batch", "seq_sp", None), aux
