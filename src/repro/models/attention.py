"""Attention: GQA (llama3/qwen3/phi3/llava/moonshot/jamba/whisper) and MLA
(minicpm3), in full-sequence (train/prefill) and decode (KV-cache) modes.

Head padding (ShardPlan): projections are built at the *padded* head count
so the head axis shards over the model mesh axis; padded heads are masked
to zero before the output projection, which keeps them exactly inert (zero
forward contribution and zero gradient) — see DESIGN.md §6.

Two compute paths: ``xla`` (pure jnp; dry-run + training) and ``pallas``
(kernels/flash_attention, interpret=True on CPU) — DESIGN.md §7.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import apply_rope, dense, dense_init, rms_norm_1d
from repro.sharding.axes import annot, constrain
from repro.sharding.rules import ShardPlan


def _head_mask(plan: ShardPlan, n_real: int) -> jax.Array:
    """[H_pad] 1.0 for real heads, 0.0 for padding heads."""
    return (jnp.arange(plan.n_heads_padded) < n_real).astype(jnp.float32)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, plan: ShardPlan) -> dict:
    d, dh = cfg.d_model, cfg.head_dim
    hq, hkv = plan.n_heads_padded, plan.n_kv_heads_padded
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, hq * dh, "embed", "heads"),
        "wk": dense_init(ks[1], d, hkv * dh, "embed", "kv_heads"),
        "wv": dense_init(ks[2], d, hkv * dh, "embed", "kv_heads"),
        "wo": dense_init(ks[3], hq * dh, d, "heads", "embed"),
    }
    if cfg.qk_norm:
        p["q_norm"] = annot(jnp.ones((dh,), jnp.float32), None)
        p["k_norm"] = annot(jnp.ones((dh,), jnp.float32), None)
    return p


def _gqa_qkv(p, cfg: ModelConfig, plan: ShardPlan, x, positions,
             rope: bool = True):
    b, s, _ = x.shape
    dh = cfg.head_dim
    hq, hkv = plan.n_heads_padded, plan.n_kv_heads_padded
    q = dense(p["wq"], x).reshape(b, s, hq, dh)
    k = dense(p["wk"], x).reshape(b, s, hkv, dh)
    v = dense(p["wv"], x).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = rms_norm_1d(q, p["q_norm"])
        k = rms_norm_1d(k, p["k_norm"])
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def _sdpa_grouped(q, k, v, qpos, kpos, causal: bool, scale: float):
    """Grouped-query attention without materializing repeated KV.

    q [B,S,Hq,dh]; k,v [B,T,Hkv,dh]; qpos [S], kpos [T] absolute positions.
    """
    b, s, hq, dh = q.shape
    hkv, t = k.shape[2], k.shape[1]
    g = hq // hkv
    q5 = q.reshape(b, s, hkv, g, dh)
    # bf16 operands + f32 accumulation: no materialized f32 copy of the
    # (potentially huge) KV cache (§Perf iteration 3)
    sc = jnp.einsum("bskgd,btkd->bkgst", q5, k,
                    preferred_element_type=jnp.float32) * scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]                # [S, T]
        sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
    m = jnp.max(sc, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    pr = jnp.exp(sc - m)
    pr = pr / jnp.maximum(jnp.sum(pr, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgst,btkd->bskgd", pr.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, s, hq, v.shape[-1]).astype(q.dtype)


_CHUNK_THRESHOLD = 2048
_Q_CHUNK = 1024
_KV_CHUNK = 1024


def _sdpa_chunked(q, k, v, qpos, kpos, causal: bool, scale: float,
                  q_chunk: int = _Q_CHUNK, kv_chunk: int = _KV_CHUNK):
    """Memory-efficient attention (Rabe-Staats double-scan; the XLA
    analogue of the flash kernel): never materializes S x T scores —
    the working set is one (q_chunk x kv_chunk) tile per step.

    The q-chunk scan body is checkpointed so backward recomputes tiles
    instead of storing them (mirrors flash backward)."""
    b, s, hq, dh = q.shape
    hkv, t = k.shape[2], k.shape[1]
    dv = v.shape[-1]
    g = hq // hkv
    qc = min(q_chunk, s)
    kc = min(kv_chunk, t)
    nq, nk = s // qc, t // kc
    qf = q.reshape(b, nq, qc, hkv, g, dh)
    kf = k.reshape(b, nk, kc, hkv, dh)
    vf = v.reshape(b, nk, kc, hkv, dv)
    qpos_c = qpos.reshape(nq, qc)
    kpos_c = kpos.reshape(nk, kc)

    @jax.checkpoint
    def per_q(_, xs):
        qi, qp = xs                                    # [b,qc,hkv,g,dh], [qc]

        def inner(carry, ys):
            m, denom, acc = carry
            ki, vi, kp = ys                            # [b,kc,hkv,dh], [kc]
            sc = jnp.einsum("bqkgd,btkd->bkgqt", qi, ki,
                            preferred_element_type=jnp.float32) * scale
            if causal:
                mask = qp[:, None] >= kp[None, :]      # [qc, kc]
                sc = jnp.where(mask[None, None, None], sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(sc - m_safe)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            denom = alpha * denom + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (m_new, denom, acc), None

        init = (jnp.full((b, hkv, g, qc, 1), -jnp.inf, jnp.float32),
                jnp.zeros((b, hkv, g, qc, 1), jnp.float32),
                jnp.zeros((b, hkv, g, qc, dv), jnp.float32))
        (m, denom, acc), _ = jax.lax.scan(
            inner, init,
            (jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0), kpos_c))
        o = acc / jnp.maximum(denom, 1e-30)                # [b,hkv,g,qc,dv]
        return None, jnp.moveaxis(o, 3, 1)             # [b,qc,hkv,g,dv]

    _, out = jax.lax.scan(per_q, None, (jnp.moveaxis(qf, 1, 0), qpos_c))
    out = jnp.moveaxis(out, 0, 1)                      # [nq->dim1]
    return out.reshape(b, s, hq, dv).astype(q.dtype)


def _sdpa(q, k, v, qpos, kpos, causal: bool, scale: float):
    """Dispatch: direct for short sequences, chunked beyond the threshold."""
    s, t = q.shape[1], k.shape[1]
    if max(s, t) > _CHUNK_THRESHOLD and s > 1 \
            and s % min(_Q_CHUNK, s) == 0 and t % min(_KV_CHUNK, t) == 0:
        return _sdpa_chunked(q, k, v, qpos, kpos, causal, scale)
    return _sdpa_grouped(q, k, v, qpos, kpos, causal, scale)


def _maybe_repeat_kv(k, v, plan: ShardPlan, g: int):
    """Beyond-paper §Perf (iteration 2): when KV heads are replicated
    (non-divisible count), the grouped einsum's kv-head dim blocks full
    head sharding and GSPMD partially replicates attention compute.
    Repeating KV to the padded q-head count (divisible by the model axis)
    restores full sharding; the repeated KV is itself head-sharded, so
    per-device bytes don't grow.

    Only applied when the kv-head count neither divides nor is divided by
    the model axis (phi3's 12 vs 16): measured on qwen3/llama3 (kv=8,
    16 % 8 == 0) GSPMD already shards the grouped form, and the repeat
    only adds HBM traffic (§Perf iteration 2, refuted sub-hypothesis)."""
    hkv = k.shape[2]
    m = plan.model_size
    if (m == 1 or plan.kv_sharded or g == 1
            or m % hkv == 0 or hkv % m == 0):
        return k, v
    k = constrain(jnp.repeat(k, g, axis=2), "batch", "seq", "heads", None)
    v = constrain(jnp.repeat(v, g, axis=2), "batch", "seq", "heads", None)
    return k, v


def gqa_full(p, cfg: ModelConfig, plan: ShardPlan, x, positions,
             causal: bool = True, impl: str = "xla"):
    """Full-sequence attention. Returns (out [B,S,d], (k, v) for caching)."""
    b, s, _ = x.shape
    dh = cfg.head_dim
    q, k, v = _gqa_qkv(p, cfg, plan, x, positions)
    scale = dh ** -0.5
    if impl == "xla":
        g = plan.n_heads_padded // plan.n_kv_heads_padded
        ka, va = _maybe_repeat_kv(k, v, plan, g)
        o = _sdpa(q, ka, va, positions, positions, causal, scale)
    else:
        from repro.kernels.flash_attention.ops import flash_attention
        o = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal,
            interpret=(impl == "pallas_interpret"),
            block_q=min(128, s), block_k=min(128, s),
        ).transpose(0, 2, 1, 3)
    o = o * _head_mask(plan, cfg.n_heads)[None, None, :, None].astype(o.dtype)
    o = o.reshape(b, s, plan.n_heads_padded * dh)
    out = dense(p["wo"], o)
    return constrain(out, "batch", "seq_sp", None), (k, v)


def gqa_decode(p, cfg: ModelConfig, plan: ShardPlan, x, cache_k, cache_v,
               pos):
    """One-token decode. x [B,1,d]; cache_k/v [B,Smax,Hkv,dh]; pos scalar.

    The KV cache's sequence dim carries the "kv_seq" logical axis: on the
    production mesh it shards over the model axis (flash-decode style
    partial attention; GSPMD inserts the LSE-merge collectives) — the
    paper's scatter-gather pattern applied to attention (DESIGN.md §3).
    """
    b = x.shape[0]
    dh = cfg.head_dim
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _gqa_qkv(p, cfg, plan, x, positions)
    seq_axes = _seqshard_axes(plan)
    if seq_axes is not None:
        cache_k, cache_v, o = _decode_attn_seqshard(
            plan, q, cache_k, cache_v, k_new, v_new, pos, dh ** -0.5,
            seq_axes)
    else:
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k_new.astype(cache_k.dtype), (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v_new.astype(cache_v.dtype), (0, pos, 0, 0))
        cache_k = constrain(cache_k, "batch", "kv_seq", "kv_heads", None)
        cache_v = constrain(cache_v, "batch", "kv_seq", "kv_heads", None)
        t = cache_k.shape[1]
        kpos = jnp.arange(t)
        # causal = "key position <= current": mask via qpos >= kpos
        o = _sdpa(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
                  positions, kpos, True, dh ** -0.5)
    o = o * _head_mask(plan, cfg.n_heads)[None, None, :, None].astype(o.dtype)
    out = dense(p["wo"], o.reshape(b, 1, -1))
    return constrain(out, "batch", None, None), cache_k, cache_v


def decode_attn_stacked(p_attn, cfg, plan: ShardPlan, x, sk, sv, layer_i,
                        pos, head_ax: str, mla: bool = False):
    """Decode attention against a *stacked* cache [n_per, B, S, H, dh],
    updating exactly one token slot at (layer_i, :, pos) in place
    (§Perf iteration 3b): carrying the stack through the layer scan avoids
    the ys write-back that rewrote a full layer slice per step.
    Returns (out [B,1,d], sk, sv)."""
    b = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    if mla:
        q, k_new, v_new = _mla_qkv(p_attn, cfg, plan, x, positions)
        scale = cfg.qk_head_dim ** -0.5
    else:
        q, k_new, v_new = _gqa_qkv(p_attn, cfg, plan, x, positions)
        scale = cfg.head_dim ** -0.5
    seq_axes = _seqshard_axes(plan)
    upd_k = k_new.astype(sk.dtype).reshape(1, b, 1, *k_new.shape[2:])
    upd_v = v_new.astype(sv.dtype).reshape(1, b, 1, *v_new.shape[2:])

    if seq_axes is None:
        sk = jax.lax.dynamic_update_slice(sk, upd_k,
                                          (layer_i, 0, pos, 0, 0))
        sv = jax.lax.dynamic_update_slice(sv, upd_v,
                                          (layer_i, 0, pos, 0, 0))
        sk = constrain(sk, None, "batch", "kv_seq", head_ax, "kv_dh")
        sv = constrain(sv, None, "batch", "kv_seq", head_ax, "kv_dh")
        ck = jax.lax.dynamic_slice(sk, (layer_i, 0, 0, 0, 0),
                                   (1,) + sk.shape[1:])[0]
        cv = jax.lax.dynamic_slice(sv, (layer_i, 0, 0, 0, 0),
                                   (1,) + sv.shape[1:])[0]
        t = ck.shape[1]
        o = _sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), positions,
                  jnp.arange(t), True, scale)
    else:
        # sequence-sharded stack: one-slot update + flash-decode LSE merge
        # inside shard_map (the paper's scatter-gather, DESIGN.md §3).
        # GSPMD would lower a DUS on the sharded dim as a whole-buffer
        # select (measured 550+ GB/step/device); the manual region writes
        # one slot and merges partial attention across shards.
        from repro.sharding.axes import spec_for
        from jax.sharding import PartitionSpec as P
        mesh = jax.sharding.get_abstract_mesh()
        rules = plan.rules_dict

        def local(q, sk, sv, kn, vn, li, pos):
            s_loc = sk.shape[2]
            idx = jax.lax.axis_index(seq_axes)
            own = (pos >= idx * s_loc) & (pos < (idx + 1) * s_loc)
            lpos = jnp.clip(pos - idx * s_loc, 0, s_loc - 1)
            cur_k = jax.lax.dynamic_slice(
                sk, (li, 0, lpos, 0, 0),
                (1, kn.shape[1], 1) + kn.shape[3:])
            cur_v = jax.lax.dynamic_slice(
                sv, (li, 0, lpos, 0, 0),
                (1, vn.shape[1], 1) + vn.shape[3:])
            sk = jax.lax.dynamic_update_slice(
                sk, jnp.where(own, kn, cur_k), (li, 0, lpos, 0, 0))
            sv = jax.lax.dynamic_update_slice(
                sv, jnp.where(own, vn, cur_v), (li, 0, lpos, 0, 0))
            ck = jax.lax.dynamic_slice(sk, (li, 0, 0, 0, 0),
                                       (1,) + sk.shape[1:])[0]
            cv = jax.lax.dynamic_slice(sv, (li, 0, 0, 0, 0),
                                       (1,) + sv.shape[1:])[0]
            bb, _, hq, dh_ = q.shape
            hkv = ck.shape[2]
            g = hq // hkv
            q5 = q.reshape(bb, 1, hkv, g, dh_)
            sc = jnp.einsum("bskgd,btkd->bkgst", q5, ck.astype(q.dtype),
                            preferred_element_type=jnp.float32) * scale
            slot = idx * s_loc + jnp.arange(s_loc)
            sc = jnp.where(slot[None, None, None, None, :] <= pos, sc,
                           -jnp.inf)
            m_g = jax.lax.pmax(jnp.max(sc, axis=-1, keepdims=True),
                               seq_axes)
            m_safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
            pr = jnp.exp(sc - m_safe)
            l_g = jax.lax.psum(jnp.sum(pr, axis=-1, keepdims=True),
                               seq_axes)
            o = jnp.einsum("bkgst,btkd->bskgd", pr.astype(q.dtype),
                           cv.astype(q.dtype),
                           preferred_element_type=jnp.float32)
            o = jax.lax.psum(o, seq_axes)                # [b,1,k,g,dv]
            o = o / jnp.maximum(l_g.transpose(0, 3, 1, 2, 4), 1e-30)
            return sk, sv, o.reshape(bb, 1, hq, -1).astype(q.dtype)

        q_spec = spec_for(("batch", None, None, None), rules)
        c_spec = spec_for((None, "batch", "kv_seq", head_ax, None), rules)
        u_spec = spec_for((None, "batch", None, None, None), rules)
        sk, sv, o = jax.shard_map(
            local, mesh=mesh, check_vma=False,
            in_specs=(q_spec, c_spec, c_spec, u_spec, u_spec, P(), P()),
            out_specs=(c_spec, c_spec, q_spec),
        )(q, sk, sv, upd_k, upd_v, layer_i, pos)

    o = o * _head_mask(plan, cfg.n_heads)[None, None, :, None].astype(
        o.dtype)
    out = dense(p_attn["wo"], o.reshape(b, 1, -1))
    return constrain(out, "batch", None, None), sk, sv


def _seqshard_axes(plan: ShardPlan):
    """Mesh axes the decode cache's sequence dim shards over (or None)."""
    rules = plan.rules_dict
    if not rules:
        return None
    r = rules.get("kv_seq")
    if r is None:
        return None
    axes = r if isinstance(r, tuple) else (r,)
    return axes if "model" in axes else None


def _decode_attn_seqshard(plan: ShardPlan, q, cache_k, cache_v, k_new,
                          v_new, pos, scale: float, seq_axes: tuple):
    """Sequence-sharded decode attention via shard_map (§Perf iteration 3).

    GSPMD lowers a dynamic_update_slice on a sharded dim as a whole-buffer
    select — every layer rewrote its entire local cache each step
    (measured 550+ GB/step/device on llama3 decode_32k). Inside shard_map
    we express what the compiler cannot prove: the owning shard writes
    exactly one slot; every shard computes partial attention over its
    local sequence chunk; partials merge with the flash-decode
    log-sum-exp reduction — the paper's scatter-gather search (§4.2)
    applied to attention.
    """
    from repro.sharding.axes import spec_for
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.get_abstract_mesh()
    rules = plan.rules_dict

    def local(q, ck, cv, kn, vn, pos):
        s_loc = ck.shape[1]
        idx = jax.lax.axis_index(seq_axes)
        own = (pos >= idx * s_loc) & (pos < (idx + 1) * s_loc)
        lpos = jnp.clip(pos - idx * s_loc, 0, s_loc - 1)
        cur_k = jax.lax.dynamic_slice_in_dim(ck, lpos, 1, 1)
        cur_v = jax.lax.dynamic_slice_in_dim(cv, lpos, 1, 1)
        ck = jax.lax.dynamic_update_slice_in_dim(
            ck, jnp.where(own, kn.astype(ck.dtype), cur_k), lpos, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cv, jnp.where(own, vn.astype(cv.dtype), cur_v), lpos, 1)
        # partial attention over the local chunk
        b, _, hq, dh_ = q.shape
        hkv = ck.shape[2]
        g = hq // hkv
        q5 = q.reshape(b, 1, hkv, g, dh_)
        sc = jnp.einsum("bskgd,btkd->bkgst", q5, ck,
                        preferred_element_type=jnp.float32) * scale
        slot = idx * s_loc + jnp.arange(s_loc)
        sc = jnp.where(slot[None, None, None, None, :] <= pos, sc, -jnp.inf)
        m_loc = jnp.max(sc, axis=-1, keepdims=True)      # [b,k,g,1,1]
        m_g = jax.lax.pmax(m_loc, seq_axes)
        m_safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
        pr = jnp.exp(sc - m_safe)
        l_g = jax.lax.psum(jnp.sum(pr, axis=-1, keepdims=True), seq_axes)
        o = jnp.einsum("bkgst,btkd->bskgd", pr.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32)
        o = jax.lax.psum(o, seq_axes)                    # [b,1,k,g,dv]
        o = o / jnp.maximum(l_g.transpose(0, 3, 1, 2, 4), 1e-30)
        return ck, cv, o.reshape(b, 1, hq, -1).astype(q.dtype)

    q_spec = spec_for(("batch", None, None, None), rules)
    c_spec = spec_for(("batch", "kv_seq",
                       "kv_heads" if plan.kv_sharded else None, None), rules)
    ck, cv, o = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(q_spec, c_spec, c_spec, q_spec, q_spec, P()),
        out_specs=(c_spec, c_spec, q_spec),
    )(q, cache_k, cache_v, k_new, v_new, pos)
    return ck, cv, o


def gqa_decode_paged(p, cfg: ModelConfig, plan: ShardPlan, x, k_pages,
                     v_pages, tables, lengths, starts, positions,
                     impl: str = "ref"):
    """One-token decode over the slab-paged KV cache (DESIGN.md §3).

    x [B,1,d]; k_pages/v_pages [n_pages, page, Hkv, dh]; tables [B, maxp]
    (the per-sequence ATT); lengths/starts [B] cache-coordinate window;
    positions [B] absolute positions for RoPE. Returns
    (out, k_pages, v_pages) — pages updated in place (donation-friendly).
    """
    b = x.shape[0]
    dh = cfg.head_dim
    page = k_pages.shape[1]
    q, k_new, v_new = _gqa_qkv(p, cfg, plan, x, positions[:, None])
    # write the new token into its slab slot (paper Alg. 2 reserve+publish;
    # slot = ATT[seq] -> (page, offset))
    pslot = lengths // page
    pidx = tables[jnp.arange(b), jnp.clip(pslot, 0, tables.shape[1] - 1)]
    ok = (pidx >= 0) & (lengths >= starts)
    tgt = jnp.where(ok, pidx, k_pages.shape[0])
    k_pages = k_pages.at[tgt, lengths % page].set(
        k_new[:, 0].astype(k_pages.dtype), mode="drop")
    v_pages = v_pages.at[tgt, lengths % page].set(
        v_new[:, 0].astype(v_pages.dtype), mode="drop")
    from repro.kernels.paged_attention.ops import paged_attention
    o = paged_attention(q[:, 0], k_pages, v_pages, tables, lengths + 1,
                        starts=starts,
                        impl="ref" if impl == "ref" else "pallas",
                        interpret=(impl == "pallas_interpret"))
    o = o * _head_mask(plan, cfg.n_heads)[None, :, None].astype(o.dtype)
    out = dense(p["wo"], o.reshape(b, 1, -1))
    return constrain(out, "batch", None, None), k_pages, v_pages


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_full(p, cfg: ModelConfig, plan: ShardPlan, x, enc_kv):
    """q from decoder x; k,v precomputed from encoder output."""
    b, s, _ = x.shape
    dh = cfg.head_dim
    hq = plan.n_heads_padded
    q = dense(p["wq"], x).reshape(b, s, hq, dh)
    q = constrain(q, "batch", "seq", "heads", None)
    k, v = enc_kv
    t = k.shape[1]
    o = _sdpa(q, k.astype(q.dtype), v.astype(q.dtype),
                      jnp.arange(s), jnp.arange(t), False, dh ** -0.5)
    o = o * _head_mask(plan, cfg.n_heads)[None, None, :, None].astype(o.dtype)
    out = dense(p["wo"], o.reshape(b, s, -1))
    return constrain(out, "batch", "seq_sp", None)


def cross_kv(p, cfg: ModelConfig, plan: ShardPlan, enc_out):
    """Precompute the encoder-side K,V once per sequence (prefill)."""
    b, t, _ = enc_out.shape
    dh = cfg.head_dim
    hkv = plan.n_kv_heads_padded
    k = dense(p["wk"], enc_out).reshape(b, t, hkv, dh)
    v = dense(p["wv"], enc_out).reshape(b, t, hkv, dh)
    return k, v


# ---------------------------------------------------------------------------
# MLA (minicpm3): multi-head latent attention
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, plan: ShardPlan) -> dict:
    d = cfg.d_model
    hq = plan.n_heads_padded
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 6)
    return {
        "w_dq": dense_init(ks[0], d, cfg.q_lora_rank, "embed", "q_lora"),
        "w_uq": dense_init(ks[1], cfg.q_lora_rank, hq * qk, "q_lora", "heads"),
        "w_dkv": dense_init(ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_dim,
                            "embed", "kv_lora"),
        "w_ukv": dense_init(ks[3], cfg.kv_lora_rank,
                            hq * (cfg.qk_nope_dim + cfg.v_head_dim),
                            "kv_lora", "heads"),
        "wo": dense_init(ks[4], hq * cfg.v_head_dim, d, "heads", "embed"),
        "q_ln": annot(jnp.ones((cfg.q_lora_rank,), jnp.float32), None),
        "kv_ln": annot(jnp.ones((cfg.kv_lora_rank,), jnp.float32), None),
    }


def _mla_qkv(p, cfg: ModelConfig, plan: ShardPlan, x, positions):
    b, s, _ = x.shape
    hq = plan.n_heads_padded
    nope, rp = cfg.qk_nope_dim, cfg.qk_rope_dim
    vh = cfg.v_head_dim
    cq = rms_norm_1d(dense(p["w_dq"], x), p["q_ln"])
    q = dense(p["w_uq"], cq).reshape(b, s, hq, nope + rp)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv = dense(p["w_dkv"], x)                               # [B,S,rank+rp]
    c_lat = rms_norm_1d(ckv[..., :cfg.kv_lora_rank], p["kv_ln"])
    k_rope = ckv[..., cfg.kv_lora_rank:].reshape(b, s, 1, rp)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)

    kv = dense(p["w_ukv"], c_lat).reshape(b, s, hq, nope + vh)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, s, hq, rp))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(v, "batch", "seq", "heads", None)
    return q, k, v


def mla_full(p, cfg: ModelConfig, plan: ShardPlan, x, positions,
             causal: bool = True, impl: str = "xla"):
    """Full-seq MLA. Cache output is the absorbed form (latent, rope_key)
    so prefill feeds the latent decode cache directly."""
    b, s, _ = x.shape
    q, k, v = _mla_qkv(p, cfg, plan, x, positions)
    scale = cfg.qk_head_dim ** -0.5
    o = _sdpa(q, k, v, positions, positions, causal, scale)
    o = o * _head_mask(plan, cfg.n_heads)[None, None, :, None].astype(o.dtype)
    out = dense(p["wo"], o.reshape(b, s, -1))
    lat = cfg.kv_lora_rank
    ckv = dense(p["w_dkv"], x)
    lat_cache = rms_norm_1d(ckv[..., :lat], p["kv_ln"])
    rope_cache = apply_rope(
        ckv[..., lat:].reshape(b, s, 1, cfg.qk_rope_dim), positions,
        cfg.rope_theta)[:, :, 0]
    return constrain(out, "batch", "seq_sp", None), (lat_cache, rope_cache)


def mla_absorbed_parts(p, cfg: ModelConfig, plan: ShardPlan, x, positions):
    """Absorbed-form MLA decode inputs (§Perf iteration 5, DeepSeek-style).

    Instead of caching expanded per-head K/V (48 heads x 160 dims per
    token), cache the shared compressed latent (256) + rope key (32):
    26.6x fewer cache bytes. Scores/outputs are mathematically exact:
      q_nope[h]·k_nope[h] = q_nope[h]·(c·W_k[h]) = (q_nope[h]·W_k[h]^T)·c
      out[h] = sum_t p_t v_t[h] = (sum_t p_t c_t)·W_v[h]
    Returns (q_comb [B,S,H,lat+rope], lat_new [B,S,lat],
    rope_new [B,S,rope]).
    """
    b, s, _ = x.shape
    hq = plan.n_heads_padded
    nope, rp = cfg.qk_nope_dim, cfg.qk_rope_dim
    lat = cfg.kv_lora_rank
    cq = rms_norm_1d(dense(p["w_dq"], x), p["q_ln"])
    q = dense(p["w_uq"], cq).reshape(b, s, hq, nope + rp)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    # absorb W_uk into q
    w_ukv = p["w_ukv"].reshape(lat, hq, nope + cfg.v_head_dim)
    w_k = w_ukv[..., :nope]                                  # [lat, H, nope]
    q_abs = jnp.einsum("bshd,lhd->bshl", q_nope,
                       w_k.astype(q_nope.dtype))             # [B,S,H,lat]
    q_comb = jnp.concatenate([q_abs, q_rope], axis=-1)
    # the new token's latent + rope key
    ckv = dense(p["w_dkv"], x)
    lat_new = rms_norm_1d(ckv[..., :lat], p["kv_ln"])        # [B,S,lat]
    rope_new = apply_rope(ckv[..., lat:].reshape(b, s, 1, rp), positions,
                          cfg.rope_theta)[:, :, 0]           # [B,S,rope]
    return q_comb, lat_new, rope_new


def mla_absorbed_out(p, cfg: ModelConfig, ctx):
    """ctx [B,S,H,lat] (attention-weighted latents) -> [B,S,H,v_head]."""
    b, s, hq, lat = ctx.shape
    nope = cfg.qk_nope_dim
    w_ukv = p["w_ukv"].reshape(lat, hq, nope + cfg.v_head_dim)
    w_v = w_ukv[..., nope:]                                  # [lat, H, vh]
    return jnp.einsum("bshl,lhv->bshv", ctx, w_v.astype(ctx.dtype))


def mla_decode_absorbed_stacked(p_attn, cfg: ModelConfig, plan: ShardPlan,
                                x, s_lat, s_rope, layer_i, pos):
    """Stacked latent-cache MLA decode: s_lat [n_per,B,S,lat],
    s_rope [n_per,B,S,rope]; one-slot update at (layer_i, :, pos)."""
    b = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    q_comb, lat_new, rope_new = mla_absorbed_parts(p_attn, cfg, plan, x,
                                                   positions)
    s_lat = jax.lax.dynamic_update_slice(
        s_lat, lat_new.astype(s_lat.dtype)[None], (layer_i, 0, pos, 0))
    s_rope = jax.lax.dynamic_update_slice(
        s_rope, rope_new.astype(s_rope.dtype)[None], (layer_i, 0, pos, 0))
    # latent dim shards over the model axis ("mlp" rule): DUS stays local
    # (pos dim unsharded); scores pay one small psum per layer
    s_lat = constrain(s_lat, None, "batch", None, "mlp")
    s_rope = constrain(s_rope, None, "batch", None, "mlp")
    lat_i = jax.lax.dynamic_slice(s_lat, (layer_i, 0, 0, 0),
                                  (1,) + s_lat.shape[1:])[0]
    rope_i = jax.lax.dynamic_slice(s_rope, (layer_i, 0, 0, 0),
                                   (1,) + s_rope.shape[1:])[0]
    keys = jnp.concatenate([lat_i, rope_i], axis=-1)[:, :, None, :]
    vals = lat_i[:, :, None, :]              # [B,S,1,lat] shared "kv head"
    t = keys.shape[1]
    o = _sdpa(q_comb, keys.astype(q_comb.dtype), vals.astype(q_comb.dtype),
              positions, jnp.arange(t), True, cfg.qk_head_dim ** -0.5)
    o = mla_absorbed_out(p_attn, cfg, o)                     # [B,1,H,vh]
    o = o * _head_mask(plan, cfg.n_heads)[None, None, :, None].astype(
        o.dtype)
    out = dense(p_attn["wo"], o.reshape(b, 1, -1))
    return constrain(out, "batch", None, None), s_lat, s_rope


def mla_decode(p, cfg: ModelConfig, plan: ShardPlan, x, cache_k, cache_v,
               pos):
    """Decode with expanded-KV cache (latent-absorbed form is a §Perf
    follow-up; DESIGN.md §2 beyond-paper list)."""
    b = x.shape[0]
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _mla_qkv(p, cfg, plan, x, positions)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k_new.astype(cache_k.dtype), (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v_new.astype(cache_v.dtype), (0, pos, 0, 0))
    cache_k = constrain(cache_k, "batch", "kv_seq", "heads", None)
    cache_v = constrain(cache_v, "batch", "kv_seq", "heads", None)
    t = cache_k.shape[1]
    o = _sdpa(q, cache_k.astype(q.dtype), cache_v.astype(q.dtype),
                      positions, jnp.arange(t), True,
                      cfg.qk_head_dim ** -0.5)
    o = o * _head_mask(plan, cfg.n_heads)[None, None, :, None].astype(o.dtype)
    out = dense(p["wo"], o.reshape(b, 1, -1))
    return constrain(out, "batch", None, None), cache_k, cache_v
