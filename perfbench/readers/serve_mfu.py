"""The whole serving step's share of the chip's peak: 2*B*M*R per
dispatched batch, summed over the window, over window time x peak FLOP/s."""

from perfbench import work


def read(run: dict, args: dict):
    spans = run.get("batch_spans")
    if not spans:
        return None
    shape = run["shape"]
    flops = sum(
        work.scored_batch_flops(rows, shape["n_items"], shape["rank"])
        for _, _, rows in spans
    )
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_per_s"])
