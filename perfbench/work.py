"""Peaks of the chip and the work an algorithm needs, counted from shapes.

The counts are of what the algorithm needs, the same whatever implements
it, so a kernel that avoids an intermediate cannot read over 100 %: a
scored batch reads the item table once, the queries in and k results
out, and does 2*B*M*R FLOPs; an ALS sweep reads the COO and both tables
once, writes the tables once, and does `als_sweep_flops` (copied from
bench.als_train_flops, which a later PR may delete).
"""

from __future__ import annotations

import json
from pathlib import Path

_PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip; an unknown device is an error."""
    with open(_PEAKS_FILE, encoding="utf-8") as f:
        table = json.load(f)["peaks"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {_PEAKS_FILE.name}: add "
            "its published peaks with their source, do not guess them"
        )
    return table[device_kind]


def als_sweep_flops(nnz: int, n_users: int, n_items: int, rank: int) -> float:
    """One ALS sweep (both halves): Gram 2*nnz*R^2 and right-hand side
    2*nnz*R per half, one (2/3)*R^3 SPD solve per row."""
    gram = 2.0 * nnz * rank * rank
    rhs = 2.0 * nnz * rank
    solve = (2.0 / 3.0) * rank ** 3
    return 2.0 * (gram + rhs) + (n_users + n_items) * solve


def als_sweep_bytes(nnz: int, n_users: int, n_items: int, rank: int,
                    id_bytes: int = 4, value_bytes: int = 4,
                    factor_bytes: int = 4) -> float:
    """One sweep: the COO (two ids and a value per rating) and both factor
    tables read once, both tables written once.  Gathered copies of rows
    are what a lowering moves, not what the algorithm needs."""
    coo = nnz * (2 * id_bytes + value_bytes)
    tables = (n_users + n_items) * rank * factor_bytes
    return float(coo + 2 * tables)


def scored_batch_flops(batch: int, n_items: int, rank: int) -> float:
    return 2.0 * batch * n_items * rank


def scored_batch_bytes(batch: int, n_items: int, rank: int, k: int,
                       factor_bytes: int = 4) -> float:
    """The item table read once, the queries in, k (value, index) out.
    The [B, M] score matrix is an intermediate and is not counted."""
    table = n_items * rank * factor_bytes
    queries = batch * rank * factor_bytes
    results = batch * k * (4 + 4)
    return float(table + queries + results)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least time the chip could take, which bound binds)."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    if t_flops >= t_bytes:
        return t_flops, "flops"
    return t_bytes, "bytes"
