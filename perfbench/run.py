"""The benchmark's command:

    python3 perfbench/run.py --workload <config>.<traffic> --seed <n>
                             --seconds <s> --trace <0|1>

One run of one cell on the machine it is started on.  The last line of
standard output is the result; the numbers `correct` compared, each beside
its limit, are the last lines of standard error and the result's last key.
Without an accelerator, or with fewer chips than the cell asks for, it
exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perfbench import cells, harness  # noqa: E402


def log(msg: str) -> None:
    print(f"[perfbench {time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def read_per_layer(cell, run: dict, root: Path) -> dict:
    """Each of the cell's per-layer metrics through its own reader; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for metric in cell.per_layer:
        value = cells.load_reader(metric.reader, root)(run, metric.args)
        if value is not None:
            out[metric.name] = {"value": float(value), "unit": metric.unit}
    return out


def execute(cell, seed: int, seconds: float, trace: bool, device: dict,
            root: Path = ROOT) -> dict:
    """Drive one run of `cell` and build the result line."""
    clock = harness.SetupClock(T_START)
    clock.phases["backend_init_s"] = time.perf_counter() - T_START
    opts = {"seed": seed, "seconds": seconds, "trace": trace,
            "clock": clock, "log": log, "device": device}
    out = cells.load_driver(cell.driver, root)(cell, opts)
    correct, compared = harness.judge(out["numbers"], cell.config["limits"])
    correct = correct and out["failed"] == 0
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"]}
    if trace:
        from perfbench import tracereduce, work

        summary = out["tracer"].reduce()
        run = dict(out["run"], trace=summary, setup=dict(clock.phases),
                   peaks=work.peaks_for(device["kind"]))
        result["metrics"] = read_per_layer(cell, run, root)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = tracereduce.breakdown(summary)
    else:
        values = dict(out["end_to_end"], setup_s=clock.setup_s)
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end
        }
    result["device"] = device
    result["info"] = out["info"]
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = cells.resolve(args.workload)
    except cells.CellError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    harness.place_compile_cache()
    try:
        device = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"perfbench: {e}; nothing falls back to the CPU",
              file=sys.stderr)
        return 3
    log(f"{cell.name} seed {args.seed} on {device}")
    result = execute(cell, args.seed, args.seconds, bool(args.trace), device)
    for c in result["compared"]:
        print(f"perfbench: compared {c['name']} = {c['value']:.6g} "
              f"(limit {c['limit']:.6g})", file=sys.stderr)
    print(f"perfbench: correct = {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
