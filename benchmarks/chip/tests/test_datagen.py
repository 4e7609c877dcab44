"""The generator: rows without ``noise_rank`` are bit for bit those of the
generator before the key existed (a frozen copy below), and rows with it lie
in a seeded orthonormal subspace with the isotropic noise's energy."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import datagen

SIFT = dict(dim=128, components=16, std=0.3, rows=8192)   # sift1m's generator
SEEDS = (2 ** 31 + 11, 2 ** 33 + 5)


# -- the generator as it was before ``noise_rank``, frozen ------------------

def seed_key(seed):
    s = int(seed) & (2 ** 64 - 1)
    return jax.random.fold_in(jax.random.key(np.uint32(s & 0xFFFFFFFF)),
                              np.uint32(s >> 32))


@partial(jax.jit, static_argnames=("dim", "components"))
def centres(key, dim, components):
    return jax.random.normal(jax.random.fold_in(key, 7), (components, dim),
                             jnp.float32)


@partial(jax.jit, static_argnames=("rows", "stream"))
def batch(key, cents, index, rows, stream, std):
    k = jax.random.fold_in(jax.random.fold_in(key, 100 + stream), index)
    kc, kn = jax.random.split(k)
    comp = jax.random.randint(kc, (rows,), 0, cents.shape[0])
    noise = jax.random.normal(kn, (rows, cents.shape[1]), jnp.float32)
    return cents[comp] + jnp.float32(std) * noise


@partial(jax.jit, static_argnames=("rows", "n", "stream"))
def _take(key, cents, first, off, rows, n, stream, std):
    two = jnp.concatenate([batch(key, cents, first, rows, stream, std),
                           batch(key, cents, first + 1, rows, stream, std)])
    return jax.lax.dynamic_slice_in_dim(two, off, n)


# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_sift_rows_and_queries_are_the_frozen_generators(seed):
    mix = datagen.Mixture(seed, **SIFT)
    assert mix.basis is None
    key = seed_key(seed)
    cents = centres(key, SIFT["dim"], SIFT["components"])
    assert np.array_equal(np.asarray(mix.cents), np.asarray(cents))
    for index, stream in ((0, datagen.ROWS), (122, datagen.ROWS),
                          (1, datagen.QUERIES)):
        got = np.asarray(mix.batch(index, stream))
        want = np.asarray(batch(key, cents, np.int32(index), SIFT["rows"],
                                stream, SIFT["std"]))
        assert np.array_equal(got, want)
    lo, n = 3 * 8192 + 5000, 8192            # spans two generator batches
    got = np.asarray(mix.take(lo, n))
    want = np.asarray(_take(key, cents, np.int32(lo // 8192),
                            np.int32(lo % 8192), 8192, n, datagen.ROWS,
                            SIFT["std"]))
    assert np.array_equal(got, want)


def test_sift_generator_programs_are_the_frozen_ones():
    """Without a basis the lowered programs are those of the frozen copy,
    so the executables (and their compile-cache entries) are unchanged."""
    key = seed_key(SEEDS[0])
    cents = centres(key, SIFT["dim"], SIFT["components"])
    i = np.int32(3)
    assert datagen.batch.lower(key, cents, i, 8192, 0, 0.3, None).as_text() \
        == batch.lower(key, cents, i, 8192, 0, 0.3).as_text()
    assert datagen._take.lower(key, cents, i, i, 8192, 8192, 0, 0.3,
                               None).as_text() \
        == _take.lower(key, cents, i, i, 8192, 8192, 0, 0.3).as_text()


def test_config_without_noise_rank_makes_the_same_mixture():
    conf = {"dim": 128, "generator": {"components": 16, "std": 0.3,
                                      "batch_rows": 8192}}
    a = datagen.Mixture.of(conf, SEEDS[0])
    b = datagen.Mixture(SEEDS[0], **SIFT)
    assert a.basis is None
    assert np.array_equal(np.asarray(a.batch(2)), np.asarray(b.batch(2)))


def test_basis_is_orthonormal_and_repeats_per_seed():
    b1 = np.asarray(datagen.basis(seed_key(SEEDS[0]), 200, 16))
    assert b1.shape == (16, 200)
    assert np.abs(b1 @ b1.T - np.eye(16)).max() < 1e-5
    b2 = np.asarray(datagen.basis(seed_key(SEEDS[0]), 200, 16))
    assert np.array_equal(b1, b2)
    b3 = np.asarray(datagen.basis(seed_key(SEEDS[1]), 200, 16))
    assert np.abs(b1 - b3).max() > 0.1


@pytest.mark.parametrize("dim,rank", [(200, 16), (960, 128), (128, 128)])
def test_low_rank_rows_lie_in_the_span(dim, rank):
    """With one component a row less its centre is its noise: it lies in
    the basis's span to float32 rounding, and its energy is the isotropic
    noise's, ``std**2 * dim`` per row."""
    std, rows = 0.3, 2048
    mix = datagen.Mixture(SEEDS[1], dim, 1, std, rows, noise_rank=rank)
    assert mix.basis.shape == (rank, dim)
    noise = np.asarray(mix.batch(4), np.float64) - np.asarray(mix.cents,
                                                               np.float64)
    b = np.asarray(mix.basis, np.float64)
    off = noise - (noise @ b.T) @ b
    scale = np.linalg.norm(noise, axis=1)
    assert np.max(np.linalg.norm(off, axis=1) / scale) < 1e-5
    energy = np.mean(np.sum(noise * noise, axis=1))
    assert energy == pytest.approx(std ** 2 * dim, rel=0.05)
    # queries share the subspace; a second run of the seed repeats them
    q = np.asarray(datagen.Mixture(SEEDS[1], dim, 1, std, rows,
                                   noise_rank=rank).batch(0, datagen.QUERIES))
    assert np.array_equal(q, np.asarray(mix.batch(0, datagen.QUERIES)))
    qn = q - np.asarray(mix.cents)
    assert np.max(np.linalg.norm(qn - (qn @ b.T) @ b, axis=1)
                  / np.linalg.norm(qn, axis=1)) < 1e-5


@pytest.mark.parametrize("rank", [0, -1, 17])
def test_noise_rank_out_of_range_is_refused(rank):
    with pytest.raises(ValueError, match="noise_rank"):
        datagen.Mixture(1, 16, 4, 0.3, 64, noise_rank=rank)


def test_dataset_seed_fixes_the_rows_and_the_run_seed_draws_the_queries():
    """With ``dataset_seed`` every run holds the same rows (centres, base
    and churn rows alike) and draws its queries from its own seed, from the
    same centres; a run's rows are the frozen generator's at that seed."""
    data = 2 ** 31 + 77
    conf = {"dim": 128, "generator": {"components": 16, "std": 0.3,
                                      "batch_rows": 8192,
                                      "dataset_seed": data}}
    a, b = (datagen.Mixture.of(conf, s) for s in SEEDS)
    key = seed_key(data)
    cents = centres(key, SIFT["dim"], SIFT["components"])
    for mix in (a, b):
        assert np.array_equal(np.asarray(mix.cents), np.asarray(cents))
        assert np.array_equal(np.asarray(mix.batch(122)), np.asarray(
            batch(key, cents, np.int32(122), 8192, datagen.ROWS, 0.3)))
    lo = 3 * 8192 + 5000
    assert np.array_equal(np.asarray(a.take(lo, 8192)),
                          np.asarray(b.take(lo, 8192)))
    for seed, mix in zip(SEEDS, (a, b)):
        want = batch(seed_key(seed), cents, np.int32(1), 8192,
                     datagen.QUERIES, 0.3)
        assert np.array_equal(np.asarray(mix.batch(1, datagen.QUERIES)),
                              np.asarray(want))
        assert np.array_equal(np.asarray(mix.take(lo, 100, datagen.QUERIES)),
                              np.asarray(_take(seed_key(seed), cents,
                                               np.int32(3), np.int32(5000),
                                               8192, 100, datagen.QUERIES,
                                               0.3)))
    assert not np.array_equal(np.asarray(a.batch(0, datagen.QUERIES)),
                              np.asarray(b.batch(0, datagen.QUERIES)))


def test_runs_of_a_fixed_dataset_build_the_same_index():
    """Two seeds of a cell whose configuration fixes the dataset train the
    same centroids on the same rows and differ in their queries only."""
    import harness
    from conftest import tiny_cell
    cell = tiny_cell(False, generator={"dataset_seed": 5})
    a, b = (harness.Deployment(cell.conf, cell.traffic, s) for s in SEEDS)
    assert np.array_equal(np.asarray(a.centroids), np.asarray(b.centroids))
    assert np.array_equal(np.asarray(a.mix.take(4000, 1024)),
                          np.asarray(b.mix.take(4000, 1024)))
    assert not np.array_equal(a.queries, b.queries)
