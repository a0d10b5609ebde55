#!/usr/bin/env python
"""Multi-host launch harness: real `jax.distributed` CPU processes.

The one process launcher every multihost test and operator drill rides:

* :func:`spawn_workers` — launches N worker processes on the CPU
  backend (whose multiprocess collectives work on the installed
  jaxlib).  The coordinator port is bound to **port 0 inside worker
  0** and published through a coordination directory
  (:func:`resolve_coordinator`) — the parent never picks a port, which
  kills the ``_free_port()`` TOCTOU race two concurrent collections
  used to lose.
* ``--demo`` — the zero-to-aha run: N real processes through the same
  path the tests use, ingesting and training over a scratch store.

The parent never imports jax; every worker is put on the CPU in the
open (``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional, Sequence

REPO_ROOT = Path(__file__).resolve().parent.parent

__all__ = [
    "resolve_coordinator",
    "spawn_workers",
    "WorkerResult",
]


# -- coordinator rendezvous -------------------------------------------------

_COORD_FILE = "coordinator_addr"


def resolve_coordinator(coord_dir, pid: int, nprocs: int,
                        timeout: float = 60.0) -> str:
    """The coordinator address for worker ``pid``, rendezvoused through
    ``coord_dir``.

    Worker 0 binds port 0 at the LAST moment (the kernel hands out a
    port no one else holds), publishes ``host:port`` atomically, and
    initializes the coordinator on it immediately; other workers poll
    the file.  Unlike a parent-side free-port scan, two concurrent
    harness runs can never be handed the same port — each run's worker 0
    owns its own bind."""
    coord_dir = Path(coord_dir)
    coord_dir.mkdir(parents=True, exist_ok=True)
    path = coord_dir / _COORD_FILE
    if pid == 0:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        addr = f"127.0.0.1:{port}"
        tmp = coord_dir / f"{_COORD_FILE}.tmp"
        tmp.write_text(addr)
        tmp.rename(path)  # atomic publish
        return addr
    deadline = time.time() + timeout
    while not path.exists():
        if time.time() > deadline:
            raise TimeoutError(
                f"coordinator address not published in {coord_dir} "
                f"within {timeout}s"
            )
        time.sleep(0.05)
    return path.read_text().strip()


# -- worker launch ----------------------------------------------------------


@dataclass
class WorkerResult:
    pid: int
    returncode: Optional[int]
    stdout: str
    stderr: str
    timed_out: bool = False

    @property
    def ok(self) -> bool:
        return (
            not self.timed_out
            and self.returncode == 0
            and f"WORKER_OK {self.pid}" in self.stdout
        )


def spawn_workers(
    nprocs: int,
    argv_of: Callable[[int], Sequence],
    *,
    worker: Optional[Path] = None,
    device_count: int = 0,
    timeout: float = 300.0,
    env_extra: Optional[dict] = None,
) -> list[WorkerResult]:
    """Launch ``nprocs`` worker processes and collect their outcomes.

    ``argv_of(pid)`` returns the worker's argv tail (stringified).
    ``device_count`` > 0 forces that many virtual CPU devices PER
    process (mesh size = nprocs * device_count), exercising the
    device→process mapping with more devices than processes.  On a
    timeout every worker is killed and the timed-out result marked —
    callers decide whether that's a failure (tests) or a report
    (operators).  Workers print ``WORKER_OK <pid>`` on success; the
    :attr:`WorkerResult.ok` property checks rc + marker."""
    worker = Path(worker) if worker else (
        REPO_ROOT / "tests" / "_multihost_worker.py"
    )
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            f"--xla_force_host_platform_device_count={device_count}"
            if device_count else ""
        ),
        **(env_extra or {}),
    }
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker)] + [str(a) for a in argv_of(p)],
            # PIO_TPU_PROCESS_INDEX stamps worker identity into every
            # span-journal filename/record (pio-tower): a cluster run's
            # journals merge and grep by worker, not by opaque pid
            env={**env, "PIO_TPU_PROCESS_INDEX": str(p)},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        for p in range(nprocs)
    ]
    results: list[WorkerResult] = []
    for p, proc in enumerate(procs):
        try:
            stdout, stderr = proc.communicate(timeout=timeout)
            results.append(
                WorkerResult(p, proc.returncode, stdout or "", stderr or "")
            )
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            results.append(WorkerResult(p, None, "", "", timed_out=True))
    return results


def _make_demo_db(path: Path):
    """Scratch sqlite event store for the real-process demo (the same
    synthetic shape the multihost tests read)."""
    import datetime as dt

    import numpy as np

    from predictionio_tpu.storage.event import DataMap, Event
    from predictionio_tpu.storage.sqlite_events import SQLiteEventStore

    rng = np.random.default_rng(0)
    es = SQLiteEventStore(path)
    es.init_channel(1)
    utc = dt.timezone.utc
    for u in range(12):
        for i in range(8):
            if rng.random() < 0.5:
                es.insert(
                    Event(
                        event="rate",
                        entity_type="user",
                        entity_id=f"u{u}",
                        target_entity_type="item",
                        target_entity_id=f"i{i}",
                        properties=DataMap(
                            {"rating": float(rng.integers(1, 6))}
                        ),
                        event_time=dt.datetime(2020, 1, 1, tzinfo=utc),
                    ),
                    app_id=1,
                )
    es.close()
    return path


# -- CLI --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--demo", action="store_true",
                    help="run the multi-process ingest+train demo")
    ap.add_argument("--nprocs", type=int, default=2)
    args = ap.parse_args(argv)
    if not args.demo:
        ap.print_help()
        return 0
    with tempfile.TemporaryDirectory(prefix="pio-mh-demo-") as td:
        td = Path(td)
        coord = td / "coord"
        # the ingest-and-train worker path over a scratch store
        sys.path.insert(0, str(REPO_ROOT))
        db = _make_demo_db(td / "events.db")
        outs = [td / f"out{p}.npz" for p in range(args.nprocs)]
        results = spawn_workers(
            args.nprocs,
            lambda p: [p, args.nprocs, coord, db, td / "exch", outs[p]],
        )
        ok = all(r.ok for r in results)
        print(json.dumps({
            "mode": "real-processes", "nprocs": args.nprocs, "ok": ok,
            "workers": [
                {"pid": r.pid, "rc": r.returncode,
                 "timed_out": r.timed_out}
                for r in results
            ],
        }, indent=2))
        return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
