"""Compile-only checks for the TPU: the served path's kernels and device
programs go through the real TPU compiler for a described (not
attached) v5e, at real row counts.  Nothing runs; a compile the chip's
compiler would refuse fails here, at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.analytics import distributed as D
from repro.core import sparse as S
from repro.kernels.spmm import spgemm_sel, spmm_ell
from repro.kernels.spmv import spmv_ell

ROWS = 1 << 20          # packet rows of a 2^20-packet Tedge block
COLS = 4096             # distinct destinations (TrafficConfig default)
K_MAX = 4
B = 8                   # fused query vectors


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    had = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if not had:
            del os.environ["TPU_LOG_DIR"]


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("ring", ["plus_times", "max_times"])
def test_spmv_ell_compiles_to_mosaic(one_chip, ring):
    txt = _compiled_text(
        lambda c, v, x: spmv_ell(c, v, x, ring=ring, interpret=False),
        _shape(one_chip, (ROWS, K_MAX), jnp.int32),
        _shape(one_chip, (ROWS, K_MAX), jnp.float32),
        _shape(one_chip, (COLS,), jnp.float32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("ring", ["plus_times", "max_times"])
def test_spmm_ell_compiles_to_mosaic(one_chip, ring):
    txt = _compiled_text(
        lambda c, v, x: spmm_ell(c, v, x, ring=ring, interpret=False),
        _shape(one_chip, (ROWS, K_MAX), jnp.int32),
        _shape(one_chip, (ROWS, K_MAX), jnp.float32),
        _shape(one_chip, (COLS, B), jnp.float32))
    assert "tpu_custom_call" in txt


def test_spgemm_sel_compiles_to_mosaic(one_chip):
    txt = _compiled_text(
        lambda c, v, s: spgemm_sel(c, v, s, interpret=False),
        _shape(one_chip, (ROWS, K_MAX), jnp.int32),
        _shape(one_chip, (ROWS, K_MAX), jnp.float32),
        _shape(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("op,x_shape", [(S.spmv, (COLS,)),
                                        (S.spmm, (COLS, B))])
def test_coo_contraction_compiles(one_chip, op, x_shape):
    """The COO path (the planner's default device lowering) as one
    jitted program; plain XLA, no custom kernel."""
    def f(rows, cols, vals, x):
        return op(S.COO(rows, cols, vals, (ROWS, COLS)), x)
    nnz = ROWS                      # one ip.dst entry per packet row
    compiled = jax.jit(f).lower(
        _shape(one_chip, (nnz,), jnp.int32),
        _shape(one_chip, (nnz,), jnp.int32),
        _shape(one_chip, (nnz,), jnp.float32),
        _shape(one_chip, x_shape, jnp.float32)).compile()
    assert compiled.memory_analysis() is not None


def test_pagerank_compiles_for_four_chips(topo):
    """The sharded PageRank program over a 4-device mesh: the nnz axis
    is split across chips and combined with all-reduces."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    rep = NamedSharding(mesh, P())
    n, nnz = 8192, ROWS
    adj = S.COO(_shape(rep, (nnz,), jnp.int32), _shape(rep, (nnz,),
                                                       jnp.int32),
                _shape(rep, (nnz,), jnp.float32), (n, n))
    txt = D._pagerank.lower(adj, _shape(rep, (n,), jnp.float32),
                            mesh=mesh, num_iters=20, damping=0.85,
                            axis="data").compile().as_text()
    assert "all-reduce" in txt
