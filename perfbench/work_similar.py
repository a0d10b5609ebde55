"""The work a scored batch with excluded ids needs, counted from shapes: the
item table read once (`[R, M]`, whichever way it lies), one candidate line
(a row of the table) gathered for each of a row's k answers and of its
excluded ids (an exact answer has to look at least at those), the queries
and their id lists in, k (value, index) out, and 2*B*M*R FLOPs.  The same
whatever implements the filter: block maxima, further gathered candidates
and a `[B, M]` score matrix or mask are intermediates and are not counted,
so a kernel that avoids them cannot read over 100 %.
"""

from __future__ import annotations


def filtered_batch_flops(batch: int, n_items: int, rank: int) -> float:
    return 2.0 * batch * n_items * rank


def filtered_batch_bytes(batch: int, n_items: int, rank: int, k: int,
                         excluded: int, factor_bytes: int = 4,
                         id_bytes: int = 4) -> float:
    table = n_items * rank * factor_bytes
    candidates = batch * (k + excluded) * rank * factor_bytes
    queries = batch * rank * factor_bytes
    lists = batch * excluded * id_bytes
    results = batch * k * (4 + id_bytes)
    return float(table + candidates + queries + lists + results)
