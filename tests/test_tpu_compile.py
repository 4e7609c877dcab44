"""The scan kernels compile for a TPU v5e at the widths the chip smoke runs.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a described ``v5e:2x2`` topology, and each test asserts
that the Pallas kernel reached the program (``tpu_custom_call``). This is
what catches block shapes, dynamic stores and SMEM use that Mosaic refuses
but the Pallas interpreter accepts.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test workers import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import filters as flt
from repro.core import index as ix
from repro.core.pq import PQConfig
from repro.core.state import SIVFConfig, init_state
from repro.kernels.sivf_scan.fused import sivf_fused_search_pallas
from repro.kernels.sivf_scan.pq_fused import sivf_pq_fused_search_pallas

# SIFT1M-shaped deployment of chip_smoke.py
D, C, K, N_SLABS, N_LISTS, MAX_CHAIN = 128, 64, 10, 24_576, 1024, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def shape(sharding, shp, dtype):
    return jax.ShapeDtypeStruct(shp, dtype, sharding=sharding)


def compiled_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def slab_planes(sh):
    return (shape(sh, (N_SLABS, C), jnp.int32),             # ids
            shape(sh, (N_SLABS, C), jnp.float32),           # norms
            shape(sh, (N_SLABS, C // 32), jnp.uint32))      # bitmap


@pytest.mark.parametrize("filtered", [False, True])
def test_fused_kernel_compiles(one_chip, filtered):
    q, t = 64, 16 * MAX_CHAIN
    args = [shape(one_chip, (q, D), jnp.float32),
            shape(one_chip, (q, t), jnp.int32),
            shape(one_chip, (N_SLABS, C, D), jnp.float32),
            *slab_planes(one_chip)]
    if not filtered:
        compiled_text(lambda *a: sivf_fused_search_pallas(*a, K), *args)
        return
    cf = flt.compile_filter(flt.And(flt.Eq("tenant", 3),
                                    flt.Range("year", 2000, 2010)),
                            ("tenant", "year"))
    args += [shape(one_chip, (N_SLABS, C, 2), jnp.int32),
             shape(one_chip, (len(cf.consts),), jnp.int32)]
    compiled_text(lambda *a: sivf_fused_search_pallas(
        *a[:6], K, attrs=a[6], fstruct=cf.structure, fconsts=a[7]), *args)


def test_pq_kernel_compiles(one_chip):
    q, t, m = 64, 16 * MAX_CHAIN, 16
    ids, _, bitmap = slab_planes(one_chip)
    compiled_text(
        lambda adc, tab, codes, ids, bm: sivf_pq_fused_search_pallas(
            adc, tab, codes, ids, bm, K),
        shape(one_chip, (q, m, 256), jnp.float32),
        shape(one_chip, (q, t), jnp.int32),
        shape(one_chip, (N_SLABS, C, m), jnp.uint8), ids, bitmap)


def test_search_dispatch_splits_for_smem(one_chip):
    """A served tile (Q=256, nprobe=16 -> T=1024) holds a 1 MiB slab table,
    more than SMEM: the dispatch must split it into kernel calls under
    the budget, and the whole search must compile."""
    nprobe, q = 16, 256
    t = nprobe * MAX_CHAIN
    assert q * t * 4 > ix.SMEM_TABLE_BYTES
    assert ix.smem_rows(t, 8) < q
    cfg = SIVFConfig(dim=D, n_lists=N_LISTS, n_slabs=N_SLABS, capacity=C,
                     max_chain=MAX_CHAIN, n_max=1 << 21)
    state = jax.eval_shape(
        lambda: init_state(cfg, jnp.zeros((N_LISTS, D), jnp.float32)))
    state = jax.tree.map(lambda x: shape(one_chip, x.shape, x.dtype), state)
    text = compiled_text(
        lambda st, qs: ix.search(cfg, st, qs, K, nprobe, impl="pallas"),
        state, shape(one_chip, (q, D), jnp.float32))
    assert "while" in text              # the lax.map over query chunks


def test_pq_search_dispatch_compiles(one_chip):
    """The PQ (m=16) index's whole search at the same tile."""
    cfg = SIVFConfig(dim=D, n_lists=N_LISTS, n_slabs=N_SLABS, capacity=C,
                     max_chain=MAX_CHAIN, n_max=1 << 21, pq=PQConfig(m=16))
    state = jax.eval_shape(
        lambda: init_state(cfg, jnp.zeros((N_LISTS, D), jnp.float32)))
    state = jax.tree.map(lambda x: shape(one_chip, x.shape, x.dtype), state)
    compiled_text(
        lambda st, qs: ix.search(cfg, st, qs, K, 16, impl="pallas"),
        state, shape(one_chip, (256, D), jnp.float32))
