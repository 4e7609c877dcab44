"""Plain reference of an IVF top-k search, and the comparison that decides
``correct``.

Nothing here imports the system under test or reads anything it made. The
rows are made again from the seed (``datagen``), the coarse quantizer is
this module's own Lloyd's k-means, and the search is a blocked brute force
over the rows that are live at a request's epoch and lie in the lists its
query probes.

Where the program may route a row, or pick a probed list, either way within
float32 rounding, the reference brackets the answer instead of guessing:

* a row whose two nearest centroids are within ``tau`` of each other may
  lie in either list ("ambiguous");
* a list whose distance to the query is within ``tau`` of the ``nprobe``-th
  may be probed or not.

The *strict* candidate set holds only rows certainly in certainly probed
lists, the *loose* set every row possibly in a possibly probed list. Every
correct answer's rank-``r`` distance lies between the loose set's and the
strict set's rank-``r`` distance, and every label it returns is in the
loose set. When no tie is near, the two sets are one and the bracket is an
equality.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from datagen import ROWS, batch

HIGHEST = jax.lax.Precision.HIGHEST
EPS32 = float(np.finfo(np.float32).eps)
# rounding allowance of one l2 expansion ||a||^2 - 2 a.b + ||b||^2 in float32,
# as a multiple of D * eps * (||a||^2 + ||b||^2); two computations (the
# program's and this one) may each be off by it
TAU_FACTOR = 4.0
EXTRA = 8            # candidates kept beyond k before the exact re-rank


def l2_expand(a, b, precision):
    """``[N, M]`` squared L2 by the expansion; ``precision`` is
    ``"f32"`` (float32 operands at HIGHEST) or ``"bf16"`` (operands rounded
    to bfloat16, the control)."""
    if precision == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
        ab = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16).T,
                        preferred_element_type=jnp.float32)
    else:
        ab = jnp.matmul(a, b.T, precision=HIGHEST)
    aa = jnp.sum(a * a, axis=-1, keepdims=True)
    bb = jnp.sum(b * b, axis=-1, keepdims=True).T
    return aa - 2.0 * ab + bb


def l2_exact(q, x):
    """Squared L2 of row pairs by elementwise differences: ``q [..., D]``,
    ``x [..., D]`` -> ``[...]``. No cancellation, float32 throughout."""
    d = q - x
    return jnp.sum(d * d, axis=-1)


@partial(jax.jit, static_argnames=("n_lists", "iters"))
def kmeans(key, xs, n_lists: int, iters: int):
    """Lloyd's k-means, float32 at HIGHEST. xs [N, D] -> [n_lists, D]."""
    idx = jax.random.choice(key, xs.shape[0], (n_lists,), replace=False)
    cents = xs[idx]

    def step(cents, _):
        assign = jnp.argmin(l2_expand(xs, cents, "f32"), axis=1)
        onehot = jax.nn.one_hot(assign, n_lists, dtype=xs.dtype)
        sums = jnp.matmul(onehot.T, xs, precision=HIGHEST)
        counts = jnp.sum(onehot, axis=0)[:, None]
        return jnp.where(counts > 0, sums / jnp.maximum(counts, 1), cents), None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return cents


@partial(jax.jit, static_argnames=("nprobe", "precision"))
def probe_sets(cents, queries, nprobe: int, precision: str):
    """Strict and loose probed-list masks ``[S, n_lists]``, and the lists
    the probe picks (``[S, nprobe]``, for the work count)."""
    d = l2_expand(queries, cents, precision)
    order = jnp.sort(d, axis=1)
    last_in, first_out = order[:, nprobe - 1:nprobe], order[:, nprobe:nprobe + 1]
    tau = tau_of(queries, cents, precision)
    loose = d <= last_in + tau
    strict = d < first_out - tau
    _, picked = jax.lax.top_k(-d, nprobe)
    return strict, loose, picked


def tau_of(a, cents, precision):
    """Per-row tie allowance ``[N, 1]`` of a distance to the centroids."""
    if precision == "bf16":
        return jnp.zeros((a.shape[0], 1), jnp.float32)
    cc = jnp.max(jnp.sum(cents * cents, axis=1))
    aa = jnp.sum(a * a, axis=1, keepdims=True)
    return TAU_FACTOR * a.shape[1] * EPS32 * (aa + cc)


def route(cents, xb, precision):
    """Nearest and second-nearest list of each row, and whether the two
    are tied within ``tau`` (then the row may lie in either)."""
    d = l2_expand(xb, cents, precision)
    neg, top2 = jax.lax.top_k(-d, 2)
    amb = (neg[:, 0] - neg[:, 1]) <= tau_of(xb, cents, precision)[:, 0]
    return top2[:, 0], top2[:, 1], amb


def _merge(best_d, best_x, best_s, d, xb, serial, kk):
    """Keep the ``kk`` nearest of the carried and the block's candidates."""
    alld = jnp.concatenate([best_d, d], axis=1)
    _, ix = jax.lax.top_k(-alld, kk)
    nd = jnp.take_along_axis(alld, ix, axis=1)
    carried = ix < kk
    bix = jnp.clip(ix - kk, 0)
    ns = jnp.where(carried, jnp.take_along_axis(best_s, jnp.clip(ix, 0, kk - 1),
                                                axis=1), serial[bix])
    nx = jnp.where(carried[..., None],
                   jnp.take_along_axis(best_x, jnp.clip(ix, 0, kk - 1)[..., None],
                                       axis=1), xb[bix])
    return nd, nx, ns


@partial(jax.jit, static_argnames=("rows", "n_gen", "kk", "precision"),
         donate_argnums=(0,))
def _pass_block(carry, key, gcents, std, basis, cents, queries, strict,
                loose, first_batch, add_ep, rem_ep, q_epoch, got_serial, *,
                rows: int, n_gen: int, kk: int, precision: str):
    """One block of ``n_gen`` generator batches against every sampled query."""
    xb = jnp.concatenate([batch(key, gcents, first_batch + i, rows, ROWS, std,
                                basis) for i in range(n_gen)])
    lo = first_batch * rows
    serial = lo + jnp.arange(xb.shape[0], dtype=jnp.int32)
    l1, l2, amb = route(cents, xb, precision)
    live = (add_ep[None, :] <= q_epoch[:, None]) \
        & (q_epoch[:, None] < rem_ep[None, :])                # [S, B]
    in_strict = live & jnp.take(strict, l1, axis=1) \
        & (~amb[None, :] | jnp.take(strict, l2, axis=1))
    in_loose = live & (jnp.take(loose, l1, axis=1)
                       | (amb[None, :] & jnp.take(loose, l2, axis=1)))
    d = l2_expand(queries, xb, precision)
    out = dict(carry)
    for name, mask in (("strict", in_strict), ("loose", in_loose),
                       ("all", live)):
        dd, xx, ss = carry[name]
        out[name] = _merge(dd, xx, ss, jnp.where(mask, d, jnp.inf), xb,
                           serial, kk)
    # the program's own answers: exact distance and membership of each
    # returned row whose serial falls in this block
    here = (got_serial >= lo) & (got_serial < lo + xb.shape[0])
    gx = xb[jnp.clip(got_serial - lo, 0, xb.shape[0] - 1)]     # [S, k, D]
    gd = l2_exact(queries[:, None, :], gx)
    gm = jnp.take_along_axis(in_loose, jnp.clip(got_serial - lo, 0,
                                                xb.shape[0] - 1), axis=1)
    out["got_d"] = jnp.where(here, gd, carry["got_d"])
    out["got_in"] = jnp.where(here, gm, carry["got_in"])
    out["max_norm"] = jnp.maximum(carry["max_norm"],
                                  jnp.max(jnp.sum(xb * xb, axis=1)))
    return out


def _init(s, k, kk, dim):
    def cand():
        return (jnp.full((s, kk), jnp.inf, jnp.float32),
                jnp.zeros((s, kk, dim), jnp.float32),
                jnp.full((s, kk), -1, jnp.int32))
    return {"strict": cand(), "loose": cand(), "all": cand(),
            "got_d": jnp.full((s, k), jnp.inf, jnp.float32),
            "got_in": jnp.zeros((s, k), bool),
            "max_norm": jnp.zeros((), jnp.float32)}


@jax.jit
def _exact_rank(queries, cand):
    """Re-rank carried candidates by exact distance: sorted distances and
    serials."""
    _, x, s = cand
    d = l2_exact(queries[:, None, :], x)
    d = jnp.where(s >= 0, d, jnp.inf)
    order = jnp.argsort(d, axis=1)
    return (jnp.take_along_axis(d, order, axis=1),
            jnp.take_along_axis(s, order, axis=1))


class Ledger:
    """Which serial holds which id, and the epochs between which it is
    live: visible to a search at epoch ``e`` iff ``add <= e < rem``."""

    NEVER = np.iinfo(np.int32).max

    def __init__(self, n_max: int):
        self.n_max = int(n_max)
        self.add = np.zeros(0, np.int32)
        self.rem = np.zeros(0, np.int32)

    @property
    def n(self) -> int:
        return len(self.add)

    def added(self, lo: int, hi: int, epoch: int) -> None:
        if hi > self.n:
            grow = hi - self.n
            self.add = np.concatenate([self.add, np.full(grow, self.NEVER,
                                                         np.int32)])
            self.rem = np.concatenate([self.rem, np.full(grow, self.NEVER,
                                                         np.int32)])
        self.add[lo:hi] = epoch

    def removed(self, lo: int, hi: int, epoch: int) -> None:
        self.rem[lo:hi] = epoch

    def ids(self, lo: int, hi: int) -> np.ndarray:
        return (np.arange(lo, hi, dtype=np.int64) % self.n_max).astype(np.int32)

    def live_at(self, epoch: int) -> np.ndarray:
        return (self.add <= epoch) & (epoch < self.rem)

    def serial_of(self, labels: np.ndarray, epochs: np.ndarray) -> np.ndarray:
        """Serial of each returned id live at its request's epoch; -1 where
        the id is not live then (or is -1)."""
        out = np.full(labels.shape, -1, np.int64)
        ok = (labels >= 0) & (labels < self.n_max)
        e = np.broadcast_to(epochs[:, None], labels.shape)
        cand = np.where(ok, labels, 0).astype(np.int64)
        while True:
            inside = ok & (cand < self.n)
            if not inside.any():
                break
            c = np.where(inside, cand, 0)
            live = inside & (self.add[c] <= e) & (e < self.rem[c])
            out = np.where(live & (out < 0), cand, out)
            cand = cand + self.n_max
        return out


def search(mix, cents, ledger: Ledger, queries, q_epoch, k: int, nprobe: int,
           *, precision: str = "f32", block_batches: int = 4):
    """The reference run in the program's place: top-k labels and
    distances by this module's routing, probe and scoring at
    ``precision``. With ``"bf16"`` it is the control."""
    res = _passes(mix, cents, ledger, queries, q_epoch, k, nprobe,
                  np.full((queries.shape[0], k), -1, np.int64), precision,
                  block_batches)
    # the plain IVF answer: the rows the routing puts in the probed lists
    # (a bf16 run has no ties: strict == loose)
    d, s = res["loose"]
    if precision == "bf16":
        dd, _, ss = res["raw_loose"]
        d, s = np.asarray(dd)[:, :k], np.asarray(ss)[:, :k]
    lab = np.where(s >= 0, (np.asarray(s) % ledger.n_max), -1).astype(np.int32)
    return np.asarray(d)[:, :k], lab


def _passes(mix, cents, ledger, queries, q_epoch, k, nprobe, got_serial,
            precision, block_batches):
    s = queries.shape[0]
    kk = k + EXTRA
    strict, loose, picked = probe_sets(cents, queries, nprobe, precision)
    carry = _init(s, k, kk, mix.dim)
    rows = mix.rows
    n_batches = -(-ledger.n // rows)
    pad = n_batches * rows + block_batches * rows
    add_ep = np.full(pad, Ledger.NEVER, np.int32)
    rem_ep = np.full(pad, Ledger.NEVER, np.int32)
    add_ep[:ledger.n] = ledger.add
    rem_ep[:ledger.n] = ledger.rem
    q_ep = jnp.asarray(q_epoch, jnp.int32)
    gs = jnp.asarray(np.clip(got_serial, -1, np.iinfo(np.int32).max), jnp.int32)
    for b0 in range(0, n_batches, block_batches):
        lo, hi = b0 * rows, (b0 + block_batches) * rows
        carry = _pass_block(carry, mix.key, mix.cents, mix.std, mix.basis,
                            cents, queries, strict, loose, np.int32(b0),
                            jnp.asarray(add_ep[lo:hi]), jnp.asarray(rem_ep[lo:hi]),
                            q_ep, gs, rows=rows, n_gen=block_batches, kk=kk,
                            precision=precision)
    out = {name: _exact_rank(queries, carry[name])
           for name in ("strict", "loose", "all")}
    out["raw_loose"] = carry["loose"]
    out["got_d"] = carry["got_d"]
    out["got_in"] = carry["got_in"]
    out["max_norm"] = carry["max_norm"]
    out["picked"] = picked
    return out


def compare(mix, cents, ledger: Ledger, queries, q_epoch, got_labels,
            got_dist, k: int, nprobe: int, block_batches: int = 4) -> dict:
    """The numbers that decide ``correct`` for sampled answers.

    ``gap``: the widest of, over every rank of every sampled answer, the
    gap between a returned distance and the exact distance of the row it
    names, the distance outside the strict/loose bracket, and a descent in
    the returned order; each over ``||q||^2 + max ||x||^2``.
    ``stray``: returned labels that are not live at the request's epoch,
    lie in no possibly probed list, repeat within an answer, or are
    missing (-1) where the strict set has a row at that rank.
    """
    got_labels = np.asarray(got_labels)
    got_dist = np.asarray(got_dist, np.float64)
    q_epoch = np.asarray(q_epoch)
    serial = ledger.serial_of(got_labels, q_epoch)
    res = _passes(mix, cents, ledger, queries, q_epoch, k, nprobe, serial,
                  "f32", block_batches)
    ds, _ = (np.asarray(x) for x in res["strict"])
    dl, _ = (np.asarray(x) for x in res["loose"])
    da, sa = (np.asarray(x) for x in res["all"])
    ds, dl = ds[:, :k].astype(np.float64), dl[:, :k].astype(np.float64)
    gd = np.asarray(res["got_d"], np.float64)
    gin = np.asarray(res["got_in"])
    qn = np.asarray(queries, np.float64)
    scale = np.sum(qn * qn, axis=1) + float(res["max_norm"])

    valid = serial >= 0
    dup = np.zeros_like(valid)
    for r in range(1, k):
        dup[:, r] = np.any(got_labels[:, :r] == got_labels[:, r:r + 1], axis=1) \
            & (got_labels[:, r] >= 0)
    missing = (got_labels < 0) & np.isfinite(ds)
    stray = ((got_labels >= 0) & (~valid | ~gin)) | dup | missing

    ok = valid & gin & ~dup
    true_d = np.where(ok, gd, np.inf)
    g_report = np.where(ok, np.abs(got_dist - true_d), 0.0)
    sorted_true = np.sort(true_d, axis=1)
    fin = np.isfinite(sorted_true)
    g_low = np.where(fin & np.isfinite(dl), np.maximum(dl - sorted_true, 0), 0)
    g_high = np.where(fin & np.isfinite(ds), np.maximum(sorted_true - ds, 0), 0)
    fd = np.where(np.isfinite(got_dist), got_dist, np.nan)
    g_order = np.nan_to_num(np.maximum(fd[:, :-1] - fd[:, 1:], 0.0))
    g_order = np.concatenate([np.zeros((len(fd), 1)), g_order], axis=1)
    gap = np.max(np.maximum.reduce([g_report, g_low, g_high, g_order]),
                 axis=1) / scale

    bf = np.where(sa[:, :k] >= 0, sa[:, :k] % ledger.n_max, -2)
    hits = sum(len(set(a[a >= 0]) & set(b[b >= 0]))
               for a, b in zip(got_labels, bf))
    return {
        "gap": float(gap.max()) if gap.size else 0.0,
        "stray": int(stray.sum()),
        "recall_at_k": hits / max(int(np.sum(bf >= 0)), 1),
        "bracket_ties": int(np.sum(ds != dl)),
        "picked": np.asarray(res["picked"]),
    }


@partial(jax.jit, static_argnames=("rows", "n_gen"))
def _route_block(key, gcents, std, basis, cents, first_batch, *, rows, n_gen):
    xb = jnp.concatenate([batch(key, gcents, first_batch + i, rows, ROWS, std,
                                basis) for i in range(n_gen)])
    return route(cents, xb, "f32")[0]


def list_rows(mix, cents, live: np.ndarray, block_batches: int = 4
              ) -> np.ndarray:
    """Live rows per list by this module's routing: ``[n_lists]``."""
    rows = mix.rows
    n_batches = -(-len(live) // rows)
    counts = np.zeros(cents.shape[0], np.int64)
    for b0 in range(0, n_batches, block_batches):
        l1 = np.asarray(_route_block(mix.key, mix.cents, mix.std, mix.basis,
                                     cents, np.int32(b0), rows=rows,
                                     n_gen=block_batches))
        lo = b0 * rows
        m = live[lo:lo + len(l1)]
        np.add.at(counts, l1[:len(m)][m], 1)
    return counts
