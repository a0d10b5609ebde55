"""Device ms a scored batch of the operations whose HLO text matches
`args["pattern"]`, in the traced window: their summed time
(`TraceSummary.op_seconds`, as `als_exchange_s` reads a sweep's) over the
batches, counted as the events of the operation named in
`args["per_batch_op"]` (one a batch).  None where the trace holds no batch
or no such operation."""


def read(run: dict, args: dict):
    trace = run.get("trace")
    if trace is None:
        return None
    _, batches = trace.op_seconds(args["per_batch_op"])
    seconds, events = trace.op_seconds(args["pattern"])
    if not batches or not events:
        return None
    return 1e3 * seconds / batches
