"""Pallas TPU kernel: segmented sum over sorted ids (D4M degree/SpMV core).

The paper's hot loop — building ``TedgeDeg`` and every semiring
contraction over the incidence matrix — reduces values into segments
given *sorted* segment ids.  GPUs do this with atomics; the TPU-native
formulation is a **one-hot matmul on the MXU**: each block of nnz values
becomes a (1, Bn) × (Bn, S_tile) product accumulated into the output tile
held in VMEM across sequential grid steps.  Irregular scatter becomes
dense systolic work — the hardware-adaptation story of DESIGN.md §2.

Grid: (segment tiles, nnz blocks); the nnz-block dimension is sequential
("arbitrary"), so accumulation into ``out_ref`` is race-free.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_NNZ = 1024      # values per grid step (8 sublanes × 128 lanes)
DEFAULT_BLOCK_SEG = 1024      # output segments per tile


def _segsum_kernel(ids_ref, vals_ref, out_ref, *, block_seg: int):
    seg_tile = pl.program_id(0)
    nnz_blk = pl.program_id(1)

    @pl.when(nnz_blk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                       # (block_nnz,) int32
    vals = vals_ref[...].astype(jnp.float32)  # (block_nnz,)
    base = seg_tile * block_seg
    local = ids - base                        # segment id within tile
    # one-hot (block_nnz, block_seg) — rows outside the tile are all-zero
    cols = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], block_seg), 1)
    onehot = (cols == local[:, None]).astype(jnp.float32)
    # (1, Bn) @ (Bn, S_tile) on the MXU
    out_ref[...] += jnp.dot(vals[None, :], onehot,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)[0]


@functools.partial(jax.jit, static_argnames=("num_segments", "block_nnz",
                                             "block_seg", "interpret"))
def segsum(ids: jax.Array, vals: jax.Array, num_segments: int,
           block_nnz: int = DEFAULT_BLOCK_NNZ,
           block_seg: int = DEFAULT_BLOCK_SEG,
           interpret: Optional[bool] = None) -> jax.Array:
    """out[s] = Σ_{i: ids[i]==s} vals[i].  ids sorted (not required for
    correctness — only for TPU memory locality).  ``interpret=None``
    compiles on TPU and interprets elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nnz = ids.shape[0]
    block_nnz = min(block_nnz, nnz)
    pad = (-nnz) % block_nnz
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=-1)  # never matches
        vals = jnp.pad(vals, (0, pad))
        nnz += pad
    seg_pad = (-num_segments) % block_seg
    n_seg = num_segments + seg_pad
    grid = (n_seg // block_seg, nnz // block_nnz)

    out = pl.pallas_call(
        functools.partial(_segsum_kernel, block_seg=block_seg),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_nnz,), lambda s, n: (n,)),
            pl.BlockSpec((block_nnz,), lambda s, n: (n,)),
        ],
        out_specs=pl.BlockSpec((block_seg,), lambda s, n: (s,)),
        out_shape=jax.ShapeDtypeStruct((n_seg,), jnp.float32),
        interpret=interpret,
    )(ids.astype(jnp.int32), vals)
    return out[:num_segments]


def _windowed_kernel(starts_ref, ids_ref, vals_ref, zeros_ref, out_ref, *,
                     block_seg: int):
    """Contribution of nnz block i to output tile starts[i] + j.

    Grid (n_blocks, 2): each sorted nnz block touches (almost always)
    only the 2 output tiles starting at its min id's tile — the
    scalar-prefetch index map places the write window, so total matmul
    work is O(nnz · 2·block_seg), independent of n_seg.  Entries outside
    the window are masked here and corrected by an exact XLA spill pass
    in the wrapper.  ``zeros_ref`` is aliased to the output for
    accumulation across window overlaps.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    del zeros_ref  # aliased with out_ref (initial zeros)
    tile = starts_ref[i] + j
    base = tile * block_seg
    ids = ids_ref[...]
    vals = vals_ref[...].astype(jnp.float32)
    local = ids - base
    cols = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], block_seg), 1)
    onehot = (cols == local[:, None]).astype(jnp.float32)
    out_ref[...] += jnp.dot(vals[None, :], onehot,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)[0]


@functools.partial(jax.jit, static_argnames=("num_segments", "block_nnz",
                                             "block_seg", "interpret"))
def segsum_windowed(ids: jax.Array, vals: jax.Array, num_segments: int,
                    block_nnz: int = DEFAULT_BLOCK_NNZ,
                    block_seg: int = DEFAULT_BLOCK_SEG,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Sorted-ids segmented sum, windowed (§Perf kernel iteration).

    The baseline kernel's one-hot matmul does O(nnz · n_seg) MXU work
    (every nnz block × every segment tile).  Sorted ids make the target
    tile computable per block — this version does O(nnz · 2·block_seg)
    with a runtime-offset output window, plus an exact spill correction
    (XLA segment_sum over the rare entries whose block spans > 2 tiles).
    """
    from jax.experimental.pallas import tpu as pltpu
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nnz = ids.shape[0]
    block_nnz = min(block_nnz, nnz)
    pad = (-nnz) % block_nnz
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), ids[-1], ids.dtype)])
        vals = jnp.pad(vals, (0, pad))
        nnz += pad
    n_blocks = nnz // block_nnz
    n_tiles = -(-num_segments // block_seg) + 2   # window overflow room
    n_seg_pad = n_tiles * block_seg

    ids_b = ids.reshape(n_blocks, block_nnz)
    starts = (ids_b[:, 0] // block_seg).astype(jnp.int32)
    # spill: entries outside the 2-tile window of their block
    in_window = (ids_b // block_seg - starts[:, None]) < 2
    vals_b = vals.reshape(n_blocks, block_nnz)
    kernel_vals = jnp.where(in_window, vals_b, 0).reshape(-1)
    spill_vals = jnp.where(in_window, 0, vals_b).reshape(-1)

    out = pl.pallas_call(
        functools.partial(_windowed_kernel, block_seg=block_seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks, 2),
            in_specs=[
                pl.BlockSpec((block_nnz,), lambda i, j, starts: (i,)),
                pl.BlockSpec((block_nnz,), lambda i, j, starts: (i,)),
                pl.BlockSpec((block_seg,),
                             lambda i, j, starts: (starts[i] + j,)),
            ],
            out_specs=pl.BlockSpec((block_seg,),
                                   lambda i, j, starts: (starts[i] + j,)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_seg_pad,), jnp.float32),
        input_output_aliases={3: 0},     # zeros init (after prefetch arg)
        interpret=interpret,
    )(starts, ids.astype(jnp.int32), kernel_vals,
      jnp.zeros((n_seg_pad,), jnp.float32))
    # exact spill correction (cheap: nearly all zeros for sorted data)
    spill = jax.ops.segment_sum(spill_vals, ids, num_segments=n_seg_pad)
    return (out + spill)[:num_segments]
