"""The work a scored batch under `categories` needs, counted from shapes: the
item table read once (`[M, R]`), ONE BIT an item a row for what the row's
categories allow (B * M / 8 bytes: an exact answer has to know of every
item whether it may be served), one candidate line (a row of the table)
gathered for each of a row's k answers and of its excluded ids, the queries,
their id lists and their category numbers in, k (value, index) out, and
2*B*M*R FLOPs.  The same whatever resident form implements the test: the
index's bit rows or lists, the words a batch's bits are formed in and how
often they are written and read again, block maxima and further gathered
candidates are what an implementation moves and are not counted, so a kernel
that avoids them cannot read over 100 %.
"""

from __future__ import annotations


def category_batch_flops(batch: int, n_items: int, rank: int) -> float:
    return 2.0 * batch * n_items * rank


def category_batch_bytes(batch: int, n_items: int, rank: int, k: int,
                         excluded: int, category_slots: int = 4,
                         factor_bytes: int = 4, id_bytes: int = 4) -> float:
    table = n_items * rank * factor_bytes
    allowed_bits = batch * n_items / 8
    candidates = batch * (k + excluded) * rank * factor_bytes
    queries = batch * rank * factor_bytes
    lists = batch * (excluded + category_slots) * id_bytes
    results = batch * k * (4 + id_bytes)
    return float(table + allowed_bits + candidates + queries + lists
                 + results)
