"""Share of its roofline that one iALS++ block sweep reaches on one chip:
the least time the chip could take for the sweep's FLOPs and bytes
(perfbench/work_subspace.py) over the traced device-busy time a sweep."""

from perfbench import work_subspace


def read(run: dict, args: dict):
    trace = run.get("trace")
    if trace is None or not run.get("traced_sweeps"):
        return None
    if "block" not in run.get("shape", {}) or trace.busy_s <= 0:
        return None
    least, _ = work_subspace.least_sweep_seconds(run["shape"], run["peaks"])
    return 100.0 * least * run["traced_sweeps"] / trace.busy_s
