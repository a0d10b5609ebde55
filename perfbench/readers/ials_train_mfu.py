"""The whole implicit-feedback training step's share of the peak of the
cell's chips together: FLOPs of a sweep (perfbench/work_ials.py) x sweeps
of the window over window time x one chip's peak FLOP/s x the chips."""

from perfbench import work_ials


def read(run: dict, args: dict):
    if not run.get("sweeps") or not run.get("chips"):
        return None
    s = run["shape"]
    flops = work_ials.ials_sweep_flops(s["nnz"], s["n_users"], s["n_items"],
                                       s["rank"])
    peak = work_ials.host_peaks(run["peaks"], run["chips"])["flops_per_s"]
    return 100.0 * flops * run["sweeps"] / (run["window_s"] * peak)
