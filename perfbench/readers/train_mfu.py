"""The whole training step's share of the chip's peak: FLOPs of a sweep x
sweeps of the window over window time x peak FLOP/s."""

from perfbench import work


def read(run: dict, args: dict):
    if not run.get("sweeps"):
        return None
    s = run["shape"]
    flops = work.als_sweep_flops(s["nnz"], s["n_users"], s["n_items"],
                                 s["rank"])
    return (100.0 * flops * run["sweeps"]
            / (run["window_s"] * run["peaks"]["flops_per_s"]))
