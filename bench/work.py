"""Operations and bytes that the algorithm needs, from the shapes alone.

The counts do not depend on the kernel that runs the work (COO segment
sums, ELL, or any later kernel): a sparse-times-dense product with
``nnz`` entries, ``rows`` x ``cols`` and ``b`` right-hand columns needs
``2 * nnz * b`` operations, and at least the bytes of one CSR read of
the matrix (a 4-byte value and a 4-byte column index per entry, a
4-byte row pointer per row) plus one float32 read of X and one float32
write of Y.  A PageRank iteration is the same product with ``b = 1``.
"""
from __future__ import annotations

F32 = 4
IDX = 4


def spmm(nnz: int, rows: int, cols: int, b: int) -> tuple:
    """(operations, bytes) of Y = A @ X with A sparse (rows x cols)."""
    flops = 2 * nnz * b
    nbytes = nnz * (F32 + IDX) + rows * IDX + cols * b * F32 + rows * b * F32
    return flops, nbytes


def pagerank_iteration(nnz: int, n: int) -> tuple:
    """(operations, bytes) of one power iteration over an n x n graph."""
    return spmm(nnz, n, n, 1)


def least_seconds(flops: float, nbytes: float, peaks) -> float:
    """The roofline's bound: the larger of compute time and memory time."""
    return max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)
