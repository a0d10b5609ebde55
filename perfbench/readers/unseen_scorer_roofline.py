"""Share of its roofline that the e-commerce scorer reaches: the least time
the chip could take for the traced batches (`perfbench/work_unseen.py`: the
table once and 2*B*M*R FLOPs, nothing for the exclusions) over their traced
device time per event of the operation named in `args["per_batch_op"]` (one
a batch).  None where the run carries no shape or the trace holds no such
event."""

from perfbench import work, work_unseen
from perfbench.readers.scorer_device_ms import scorer_batches


def read(run: dict, args: dict):
    total, batches = scorer_batches(run, args)
    spans = run.get("traced_batch_spans")
    shape = run.get("shape")
    if total is None or not spans or not shape:
        return None
    least = sum(
        work.least_seconds(
            work_unseen.unseen_batch_flops(rows, shape["n_items"],
                                           shape["rank"]),
            work_unseen.unseen_batch_bytes(rows, shape["n_items"],
                                           shape["rank"], shape["k"]),
            run["peaks"],
        )[0]
        for _, _, rows in spans
    ) / len(spans)
    return 100.0 * least / (total / batches)
