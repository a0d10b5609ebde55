"""Work of one implicit-feedback ALS sweep (Hu, Koren, Volinsky), counted
from shapes, and the peaks of a host of several chips.

As `work.als_sweep_flops` plus the two `YtY`s: a half adds the whole
opposite table's `Y^T Y` (2 M R^2) to every row's Gram.  Bytes: the COO
and both tables read once and both tables written once in the chips'
memories (`work.als_sweep_bytes`); and, where the tables are sharded
over more than one chip, each table crosses the interconnect once a
sweep, which is what any placement that solves a row on one chip from
rows that live on others has to move at least.  The counts are of what
the algorithm needs, so an exchange that moves more cannot read over
100 %.
"""

from __future__ import annotations

from perfbench import work


def ials_sweep_flops(nnz: int, n_users: int, n_items: int, rank: int) -> float:
    return (work.als_sweep_flops(nnz, n_users, n_items, rank)
            + 2.0 * (n_users + n_items) * rank * rank)


def ials_sweep_hbm_bytes(nnz: int, n_users: int, n_items: int,
                         rank: int) -> float:
    return work.als_sweep_bytes(nnz, n_users, n_items, rank)


def ials_sweep_ici_bytes(n_users: int, n_items: int, rank: int, chips: int,
                         factor_bytes: int = 4) -> float:
    """Each table once over the interconnect; nothing on one chip."""
    if chips <= 1:
        return 0.0
    return float((n_users + n_items) * rank * factor_bytes)


def host_peaks(peaks: dict, chips: int) -> dict:
    """Peaks of `chips` chips together: FLOP/s and memory bytes/s add."""
    return {"flops_per_s": peaks["flops_per_s"] * chips,
            "bytes_per_s": peaks["bytes_per_s"] * chips}


def least_seconds(shape: dict, peaks: dict, chips: int) -> tuple:
    """(least time `chips` chips could take for one sweep, which bound
    binds).  `peaks.json` gives no rate for the interconnect, so the
    interconnect's bytes are counted (`ials_sweep_ici_bytes`) and bound
    nothing here."""
    dims = (shape["nnz"], shape["n_users"], shape["n_items"], shape["rank"])
    return work.least_seconds(
        ials_sweep_flops(*dims), ials_sweep_hbm_bytes(*dims),
        host_peaks(peaks, chips),
    )
