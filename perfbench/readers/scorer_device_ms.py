"""Device time per scored batch in the traced window: the summed time of
every device operation over the number of batches, counted as the events of
the operation named in `args["per_batch_op"]` (one per batch)."""


def scorer_batches(run: dict, args: dict):
    trace = run.get("trace")
    if trace is None:
        return None, None
    total = sum(ns for _, ns, _ in trace.ops) / 1e9
    _, batches = trace.op_seconds(args["per_batch_op"])
    if batches == 0 or total <= 0:
        return None, None
    return total, batches


def read(run: dict, args: dict):
    total, batches = scorer_batches(run, args)
    if total is None:
        return None
    return 1e3 * total / batches
