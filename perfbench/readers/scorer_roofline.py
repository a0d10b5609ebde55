"""Share of its roofline that the scorer reaches: the least time the chip
could take for the traced batches (FLOPs and bytes the algorithm needs,
counted from shapes in perfbench/work.py) over their traced device time."""

from perfbench import work
from perfbench.readers.scorer_device_ms import scorer_batches


def read(run: dict, args: dict):
    total, batches = scorer_batches(run, args)
    spans = run.get("traced_batch_spans")
    if total is None or not spans:
        return None
    shape = run["shape"]
    least = sum(
        work.least_seconds(
            work.scored_batch_flops(rows, shape["n_items"], shape["rank"]),
            work.scored_batch_bytes(rows, shape["n_items"], shape["rank"],
                                    shape["k"]),
            run["peaks"],
        )[0]
        for _, _, rows in spans
    ) / len(spans)
    return 100.0 * least / (total / batches)
