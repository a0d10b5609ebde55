"""Share of its roofline that the scorer under `categories` reaches: the
least time the chip could take for the traced batches
(`perfbench/work_simcat.py`: the table once, one bit an item a row, the
chosen candidates' rows, 2*B*M*R FLOPs) over their traced device time per
event of the operation named in `args["per_batch_op"]` (one a batch).  None
where the run carries no filtered shape (`shape["excluded"]`) or the trace
holds no such event."""

from perfbench import work, work_simcat
from perfbench.readers.scorer_device_ms import scorer_batches


def read(run: dict, args: dict):
    total, batches = scorer_batches(run, args)
    spans = run.get("traced_batch_spans")
    shape = run.get("shape") or {}
    if total is None or not spans or "excluded" not in shape:
        return None
    least = sum(
        work.least_seconds(
            work_simcat.category_batch_flops(rows, shape["n_items"],
                                             shape["rank"]),
            work_simcat.category_batch_bytes(rows, shape["n_items"],
                                             shape["rank"], shape["k"],
                                             shape["excluded"]),
            run["peaks"],
        )[0]
        for _, _, rows in spans
    ) / len(spans)
    return 100.0 * least / (total / batches)
