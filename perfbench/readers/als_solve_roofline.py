"""Share of its roofline that the batched SPD solve kernel reaches: the
least time the chip could take for the FLOPs and bytes of the systems a
sweep solves (perfbench/work_subspace.py; `solve_systems` of width
`block`, batch padding included, as the program counts them) over the
kernel's traced device time a sweep (ops matching `args["pattern"]`).
None where the run names no systems or the trace holds no such op."""

from perfbench import work_subspace


def read(run: dict, args: dict):
    trace = run.get("trace")
    systems = run.get("solve_systems")
    if trace is None or not run.get("traced_sweeps") or not systems:
        return None
    seconds, events = trace.op_seconds(args["pattern"])
    if not events or seconds <= 0:
        return None
    least, _ = work_subspace.least_solve_seconds(
        systems, run["shape"]["block"], run["peaks"])
    per_sweep = seconds / (trace.n_devices * run["traced_sweeps"])
    return 100.0 * least / per_sweep
