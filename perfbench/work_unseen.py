"""The work a scored batch of the e-commerce engine needs, counted from
shapes: the item table read once (`[M, R]`), the queries in, k (value,
index) out, and 2*B*M*R FLOPs.  NOTHING for the exclusions: a query's seen
and unavailable items are a list of ids that an exact answer could apply to
the scores it has; the listed ids' gathered blocks, their sort, the
corrected maxima and any `[B, M]` mask are what an implementation moves, so
the count reads the same work whatever implements the filter and a batch
with a long list reads a smaller share, never one over 100 %.
"""

from __future__ import annotations


def unseen_batch_flops(batch: int, n_items: int, rank: int) -> float:
    return 2.0 * batch * n_items * rank


def unseen_batch_bytes(batch: int, n_items: int, rank: int, k: int,
                       factor_bytes: int = 4, id_bytes: int = 4) -> float:
    table = n_items * rank * factor_bytes
    queries = batch * rank * factor_bytes
    results = batch * k * (4 + id_bytes)
    return float(table + queries + results)
