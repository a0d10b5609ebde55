"""The whole block-sweep training step's share of one chip's peak: FLOPs
of a sweep (perfbench/work_subspace.py) x sweeps of the window over
window time x peak FLOP/s."""

from perfbench import work_subspace


def read(run: dict, args: dict):
    s = run.get("shape", {})
    if not run.get("sweeps") or "block" not in s:
        return None
    flops = work_subspace.sweep_flops(s["nnz"], s["n_users"], s["n_items"],
                                      s["rank"], s["block"])
    return (100.0 * flops * run["sweeps"]
            / (run["window_s"] * run["peaks"]["flops_per_s"]))
