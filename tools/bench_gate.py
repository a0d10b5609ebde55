#!/usr/bin/env python
"""Noise-aware perf-regression gate over BENCH_HISTORY.jsonl.

The bench trajectory has existed since round 2 (``BENCH_HISTORY.jsonl``
— one JSON record per honest, *fenced* measurement) but nothing ever
read it: a 3x train-time regression would sail through the gate as long
as tests stayed green.  This tool closes the loop:

* ``--append FILE``  — canonicalize a bench result (the JSON line
  ``bench.py`` prints / a ``BENCH_PR<k>.json`` summary) and append it
  to the history in the established schema (``metric``, ``value``,
  ``unit``, ``vs_baseline``, ``platform``, ``scale``, ``recorded_at``,
  ``fenced`` + measurement extras).
* ``--check [FILE]`` — compare a candidate (default: the newest
  comparable record in the history) against a **rolling-median
  baseline with a noise-aware threshold**:

  - baseline = median of the last ``--window`` comparable records with
    the same ``(metric, platform, scale)`` key — *fenced* records only
    (an unfenced number measured dispatch, not compute);
  - noise    = the robust sigma ``1.4826 * MAD`` of those records;
  - fail when ``value > median + max(min_rel * median,
    noise_mult * sigma)`` — a quiet history gets a tight gate, a noisy
    one a proportionally loose one, and a min-sample guard (``--min-samples``) keeps a
    2-point "trend" from ever failing anyone.

Exit codes: 0 pass, 1 regression, 2 not checkable (no candidate /
insufficient history / unfenced candidate) — ``--allow-empty`` turns 2
into 0 so CI can adopt the gate before the trajectory is deep enough
to judge (``tools/gate.sh`` runs ``--check --allow-empty``).

Also the shared writer for the canonical per-PR bench summary
(``BENCH_PR<k>.json``): ``bench.py`` writes the train record at the top
level, ``bench_serving.py`` merges its record under ``"serving"`` —
same fields as a history record either way, so the harness reads one
schema everywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from statistics import median
from typing import Optional

REPO_ROOT = Path(__file__).resolve().parent.parent
DEFAULT_HISTORY = REPO_ROOT / "BENCH_HISTORY.jsonl"

CANONICAL_FIELDS = (
    "metric", "value", "unit", "vs_baseline", "platform", "scale",
    "nproc", "recorded_at", "fenced",
)


# -- records ---------------------------------------------------------------


def canonical_record(rec: dict, fenced: Optional[bool] = None) -> dict:
    """History-schema record: the canonical fields (always present, in
    order) followed by whatever measurement extras the source carried.
    ``fenced`` defaults to the record's own claim — never guessed True:
    an unfenced timing is a dispatch time, not a measurement."""
    out = {
        "metric": rec.get("metric"),
        "value": rec.get("value"),
        "unit": rec.get("unit", "s"),
        "vs_baseline": rec.get("vs_baseline"),
        "platform": rec.get("platform"),
        "scale": rec.get("scale"),
        # the box's core count is part of the measurement identity:
        # a multi-worker number from a 1-core box (workers time-slice
        # one core) must never baseline a real multi-core run
        "nproc": int(rec.get("nproc") or os.cpu_count() or 1),
        "recorded_at": rec.get("recorded_at") or time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        "fenced": bool(
            rec.get("fenced") if fenced is None else fenced
        ),
    }
    out.update({
        k: v for k, v in rec.items() if k not in out
    })
    return out


def load_history(path: Path) -> list:
    """Parse the JSONL history, skipping malformed lines (the history
    is appended by many tools across rounds; one bad line must not
    disable the gate)."""
    if not path.exists():
        return []
    out = []
    for ln in path.read_text().splitlines():
        ln = ln.strip()
        if not ln:
            continue
        try:
            out.append(json.loads(ln))
        except json.JSONDecodeError:
            continue
    return out


def append_history(path: Path, rec: dict) -> dict:
    rec = canonical_record(rec)
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(rec) + "\n")
    return rec


def baseline_key(rec: dict) -> tuple:
    """Records are only comparable at the same metric, platform,
    problem scale and core count — a CPU-fallback number next to a TPU
    number is the exact confusion the LOUD-fallback contract exists to
    prevent, and a 1-core multi-worker number next to a 32-core one is
    its ingest-side twin.  Records written before ``nproc`` existed
    key at 0 ("unknown box"): the history shows the same metric
    swinging 334 -> 1473 QPS across sessions, so legacy records have
    unknowable core provenance — they keep judging each other but
    never judge a stamped run, and each stamped core count starts its
    own rolling baseline."""
    return (
        rec.get("metric"),
        rec.get("platform") or "",
        float(rec.get("scale") or 0.0),
        int(rec.get("nproc") or 0),
    )


def comparable(rec: dict) -> bool:
    v = rec.get("value")
    return (
        rec.get("fenced") is True
        and isinstance(v, (int, float))
        and v > 0
    )


# metric-name fallbacks for records written before the explicit
# ``direction`` field existed; throughput-shaped names gate upward
_UP_HINTS = ("qps", "_per_s", "throughput", "events_per")


def metric_direction(rec: dict) -> str:
    """Which way is worse for this metric: ``down`` (latency/seconds —
    a regression is a LARGER value, the original gate semantics) or
    ``up`` (throughput — a regression is a SMALLER value).  The
    record's explicit ``direction`` field wins; otherwise the metric
    name decides, so pre-existing history records need no rewrite."""
    d = rec.get("direction")
    if d in ("up", "down"):
        return d
    m = str(rec.get("metric") or "")
    return "up" if any(h in m for h in _UP_HINTS) else "down"


# -- the check -------------------------------------------------------------


def check_candidate(
    history: list,
    candidate: dict,
    window: int = 8,
    min_samples: int = 3,
    noise_mult: float = 4.0,
    min_rel: float = 0.10,
) -> dict:
    """Judge one candidate record against the rolling baseline.

    Returns a verdict dict with ``status`` in {"ok", "regression",
    "insufficient", "unfenced"} plus the threshold math, so the gate
    log shows *why* — a gate that just says FAIL teaches nobody.
    """
    if not comparable(candidate):
        return {
            "status": "unfenced",
            "reason": "candidate is unfenced or has no numeric value; "
                      "only fenced device-complete timings are judged",
            "candidate": candidate.get("value"),
        }
    key = baseline_key(candidate)
    base = [
        float(r["value"]) for r in history
        if comparable(r) and baseline_key(r) == key and r is not candidate
    ][-window:]
    if len(base) < min_samples:
        return {
            "status": "insufficient",
            "reason": f"need >= {min_samples} fenced baseline records "
                      f"for {key}, have {len(base)}",
            "nSamples": len(base),
            "key": list(key),
        }
    med = median(base)
    mad = median(abs(v - med) for v in base)
    sigma = 1.4826 * mad  # robust sigma: MAD -> stddev for a normal
    margin = max(min_rel * med, noise_mult * sigma)
    value = float(candidate["value"])
    # same rolling-median + MAD math both ways; only the failing side
    # flips — a throughput (direction=up) collapse gates exactly like a
    # latency blow-up
    direction = metric_direction(candidate)
    if direction == "up":
        threshold = med - margin
        regressed = value < threshold
    else:
        threshold = med + margin
        regressed = value > threshold
    return {
        "status": "regression" if regressed else "ok",
        "key": list(key),
        "direction": direction,
        "value": value,
        "baselineMedian": med,
        "robustSigma": sigma,
        "noiseMult": noise_mult,
        "minRel": min_rel,
        "threshold": threshold,
        "ratio": value / med if med else None,
        "nSamples": len(base),
        "window": window,
    }


# -- BENCH_PR<k>.json summary ----------------------------------------------


def pr_number() -> int:
    """This PR's ordinal: ``PIO_TPU_PR`` wins; otherwise one past the
    PR entries already logged in CHANGES.md (one line each)."""
    env = os.environ.get("PIO_TPU_PR")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    changes = REPO_ROOT / "CHANGES.md"
    try:
        n = sum(
            1 for ln in changes.read_text().splitlines()
            if ln.strip().startswith("- PR")
        )
        return n + 1
    except OSError:
        return 0


def pr_summary_path(k: Optional[int] = None) -> Path:
    """``PIO_TPU_PR_SUMMARY`` redirects the summary wholesale (tests
    point it at a tmp dir so a stubbed bench run can never clobber the
    real repo-root artifact); otherwise BENCH_PR<k>.json at the root."""
    env = os.environ.get("PIO_TPU_PR_SUMMARY")
    if env:
        return Path(env)
    return REPO_ROOT / f"BENCH_PR{pr_number() if k is None else k}.json"


def write_pr_summary(rec: dict, key: Optional[str] = None,
                     path: Optional[Path] = None) -> Path:
    """Merge a canonical record into the PR summary file.  ``key=None``
    writes the record's fields at the top level (bench.py's train
    number — the primary trajectory metric); a key nests it (e.g.
    ``"serving"``) without clobbering what the other bench wrote."""
    path = path or pr_summary_path()
    existing = {}
    if path.exists():
        try:
            existing = json.loads(path.read_text())
        except (json.JSONDecodeError, OSError):
            existing = {}
    rec = canonical_record(rec)
    if key is None:
        nested = {
            k: v for k, v in existing.items()
            if isinstance(v, dict) and k not in rec
        }
        existing = {**rec, **nested}
    else:
        existing[key] = rec
    path.write_text(json.dumps(existing, indent=1) + "\n")
    return path


# -- cli -------------------------------------------------------------------


def _load_candidate(spec: str) -> dict:
    """A candidate record from a file path or '-' (stdin).  Accepts a
    single JSON object, or JSONL (the last parseable line wins — the
    bench prints warnings before its one JSON line)."""
    text = (
        sys.stdin.read() if spec == "-" else Path(spec).read_text()
    )
    try:
        rec = json.loads(text)
        if not isinstance(rec, dict):
            raise ValueError(
                f"candidate in {spec!r} is {type(rec).__name__}, "
                "expected a JSON object"
            )
        return rec
    except json.JSONDecodeError:
        rec = None
        for ln in text.splitlines():
            ln = ln.strip()
            if not ln or not ln.startswith("{"):
                continue
            try:
                rec = json.loads(ln)
            except json.JSONDecodeError:
                continue
        if rec is None:
            raise ValueError(f"no JSON record found in {spec!r}")
        return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--history", type=Path, default=DEFAULT_HISTORY)
    ap.add_argument("--append", metavar="FILE",
                    help="canonicalize FILE ('-' = stdin) and append "
                    "it to the history")
    ap.add_argument("--check", nargs="?", const="", metavar="FILE",
                    help="judge FILE (default: newest comparable "
                    "history record) against the rolling baseline")
    ap.add_argument("--allow-empty", action="store_true",
                    help="exit 0 when there is nothing to judge "
                    "(short/empty history, unfenced candidate)")
    ap.add_argument("--window", type=int, default=8,
                    help="baseline = rolling median of the last N "
                    "comparable records (default 8)")
    ap.add_argument("--min-samples", type=int, default=3,
                    help="minimum baseline records before the gate "
                    "judges at all (default 3)")
    ap.add_argument("--noise-mult", type=float, default=4.0,
                    help="threshold margin in robust sigmas "
                    "(default 4)")
    ap.add_argument("--min-rel", type=float, default=0.10,
                    help="threshold margin floor as a fraction of the "
                    "baseline median (default 0.10)")
    args = ap.parse_args(argv)

    if args.append is not None:
        try:
            rec = append_history(
                args.history, _load_candidate(args.append)
            )
        except (ValueError, OSError) as e:
            print(json.dumps({"status": "error", "reason": str(e)}))
            return 2
        print(json.dumps({"appended": rec,
                          "history": str(args.history)}))
        return 0

    if args.check is None:
        ap.error("one of --append/--check is required")

    history = load_history(args.history)
    if args.check:
        # an explicitly named candidate that can't be read/parsed is an
        # operator error, not an empty trajectory: exit 2 regardless of
        # --allow-empty (a typo'd path must never turn the gate green)
        try:
            candidate = canonical_record(_load_candidate(args.check))
        except (ValueError, OSError) as e:
            print(json.dumps({"status": "error", "reason": str(e)}))
            return 2
    else:
        candidates = [r for r in history if comparable(r)]
        if not candidates:
            verdict = {
                "status": "insufficient",
                "reason": "history has no comparable (fenced, "
                          "numeric) record to judge",
            }
            print(json.dumps(verdict, indent=1))
            return 0 if args.allow_empty else 2
        candidate = candidates[-1]
        # the newest record must not sit in its own baseline
        history = [r for r in history if r is not candidate]

    verdict = check_candidate(
        history, candidate,
        window=args.window, min_samples=args.min_samples,
        noise_mult=args.noise_mult, min_rel=args.min_rel,
    )
    print(json.dumps(verdict, indent=1))
    if verdict["status"] == "ok":
        return 0
    if verdict["status"] == "regression":
        return 1
    return 0 if args.allow_empty else 2


if __name__ == "__main__":
    sys.exit(main())
