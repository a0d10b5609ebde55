"""Share of its roofline that an ALS sweep reaches: the least time the chip
could take for one sweep's FLOPs and bytes (perfbench/work.py) over the
traced device-busy time per sweep."""

from perfbench import work


def read(run: dict, args: dict):
    trace = run.get("trace")
    if trace is None or not run.get("traced_sweeps"):
        return None
    s = run["shape"]
    dims = (s["nnz"], s["n_users"], s["n_items"], s["rank"])
    least, _ = work.least_seconds(
        work.als_sweep_flops(*dims), work.als_sweep_bytes(*dims), run["peaks"]
    )
    return 100.0 * least * run["traced_sweeps"] / trace.busy_s
