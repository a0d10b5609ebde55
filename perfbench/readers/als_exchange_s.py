"""Device seconds a sweep that one chip spends in the operations that move
data between chips (all-gather, all-reduce, reduce-scatter,
collective-permute, all-to-all, and the fusions the TPU's compiler makes
of them): the trace's per-operation sums over every chip, divided by the
chips and the traced sweeps.  None where the trace holds no such
operation (one chip, or a program that exchanges nothing)."""

COLLECTIVES = (r"^%?(all-gather|all-reduce|reduce-scatter|"
               r"collective-permute|all-to-all)|calls=%?all-reduce-scatter")


def read(run: dict, args: dict):
    trace = run.get("trace")
    if trace is None or not run.get("traced_sweeps"):
        return None
    seconds, events = trace.op_seconds(args.get("pattern", COLLECTIVES))
    if not events:
        return None
    return seconds / (trace.n_devices * run["traced_sweeps"])
