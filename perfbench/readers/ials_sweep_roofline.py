"""Share of its roofline that an implicit-feedback ALS sweep reaches on
the cell's chips together: the least time that many chips could take for
one sweep's FLOPs and bytes (perfbench/work_ials.py, the peaks of one
chip times the chips) over the traced device-busy time per sweep, which
is already the mean over the chips."""

from perfbench import work_ials


def read(run: dict, args: dict):
    trace = run.get("trace")
    if trace is None or not run.get("traced_sweeps") or not run.get("chips"):
        return None
    if trace.busy_s <= 0:
        return None
    least, _ = work_ials.least_seconds(run["shape"], run["peaks"],
                                       run["chips"])
    return 100.0 * least * run["traced_sweeps"] / trace.busy_s
