"""Work of one iALS++ block sweep (arXiv 2110.14044) of implicit-feedback
ALS, both halves, counted from shapes: what the algorithm needs at an
embedding dimension R swept in blocks of w coordinates, the same whatever
implements it.

A half over nnz entries, n rows solved and m opposite rows:

    Y^T Y of the opposite table              2 m R^2
    the prediction p = Y_u x, once           2 nnz R
    the cache q = x Y^T Y, once              2 n R^2
    and for each of the R / w blocks
      the block Hessians                     2 nnz w^2
      the block gradients                    2 nnz w
      one SPD solve of width w a row         (2/3) w^3   (as work.py counts)
      p advanced by the step                 2 nnz w
      q advanced by the step                 2 n w R

Bytes: the COO and both tables read once, both tables written once
(`work.als_sweep_bytes`): gathered copies of rows are what a lowering
moves, not what the algorithm needs.  The solve kernel alone: a system
of width w is read once (w^2 + w values) and its solution written (w).
"""

from __future__ import annotations

from perfbench import work


def half_flops(nnz: int, n_rows: int, n_opposite: int, rank: int,
               block: int) -> float:
    blocks = -(-rank // block)
    once = (2.0 * n_opposite * rank * rank + 2.0 * nnz * rank
            + 2.0 * n_rows * rank * rank)
    a_block = (2.0 * nnz * block * block + 4.0 * nnz * block
               + n_rows * solve_flops(block) + 2.0 * n_rows * block * rank)
    return once + blocks * a_block


def sweep_flops(nnz: int, n_users: int, n_items: int, rank: int,
                block: int) -> float:
    return (half_flops(nnz, n_users, n_items, rank, block)
            + half_flops(nnz, n_items, n_users, rank, block))


def sweep_bytes(nnz: int, n_users: int, n_items: int, rank: int) -> float:
    return work.als_sweep_bytes(nnz, n_users, n_items, rank)


def solve_flops(width: int) -> float:
    """One SPD system of `width`: the factorisation as `work.py` counts
    it, and the two substitutions."""
    return (2.0 / 3.0) * width ** 3 + 2.0 * width ** 2


def solve_bytes(width: int, value_bytes: int = 4) -> float:
    return float((width * width + 2 * width) * value_bytes)


def least_sweep_seconds(shape: dict, peaks: dict) -> tuple:
    """(least time one chip could take for one sweep, which bound binds)."""
    dims = (shape["nnz"], shape["n_users"], shape["n_items"], shape["rank"])
    return work.least_seconds(sweep_flops(*dims, shape["block"]),
                              sweep_bytes(*dims), peaks)


def least_solve_seconds(systems: int, width: int, peaks: dict) -> tuple:
    """(least time one chip could take for `systems` solves of `width`,
    which bound binds)."""
    return work.least_seconds(systems * solve_flops(width),
                              systems * solve_bytes(width), peaks)
