"""The two gathers the ALS hot loop can use, as timeable probes.

ROADMAP D1 leaves one gather A/B open: ``ALSConfig(gather_mode=
"grouped")`` against the row gather.  This module times both on
identical float32 shapes:

  * ``probe_xla_take`` — the XLA ``jnp.take`` row gather (what the ALS
    hot loop pays by default);
  * ``probe_xla_grouped_take`` — the tile-slab gather behind
    ``gather_mode="grouped"``: ``[G, R]`` slabs of the 3D view
    ``[M/G, G, R]``, then an in-slab select.

On the CPU the times say nothing about the chip; ``smoke`` checks that
both forms gather the right rows (the gate's step).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["probe_xla_grouped_take", "probe_xla_take", "smoke"]

# rows a slab: a float32 tile's sublanes, models/als._GATHER_GROUP_ROWS
_GROUP = 8


def _bench(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def _table_and_ids(m, nout, r):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(m, r)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, m, size=(nout,)).astype(np.int32))
    return table, idx


def probe_xla_take(m, nout, r) -> dict:
    table, idx = _table_and_ids(m, nout, r)
    dt, _ = _bench(jax.jit(lambda t, i: jnp.take(t, i, axis=0)),
                   table, idx)
    return dict(metric="xla_take", m=m, nout=nout, r=r, seconds=dt,
                ns_per_row=dt / nout * 1e9,
                effective_gbps=nout * r * 4 / dt / 1e9)


def probe_xla_grouped_take(m, nout, r) -> dict:
    """The grouped slab gather (production's form): gather ``[G, R]``
    slices of the 3D view ``[M/G, G, R]``, whose trailing dims are the
    tiled ones, so one gathered slice is whole tiles; ``ok`` says
    whether it returned the row take's rows."""
    mg = -(-m // _GROUP) * _GROUP
    table, idx = _table_and_ids(mg, nout, r)
    idx = idx % m

    def grouped(t, i):
        g = jnp.take(t.reshape(mg // _GROUP, _GROUP, r), i // _GROUP,
                     axis=0)
        sel = jnp.broadcast_to((i % _GROUP)[:, None, None], (nout, 1, r))
        return jnp.take_along_axis(g, sel, axis=1)[:, 0, :]

    dt, got = _bench(jax.jit(grouped), table, idx)
    good = bool(np.array_equal(np.asarray(got),
                               np.asarray(table)[np.asarray(idx)]))
    return dict(metric="xla_grouped3d_take", m=m, nout=nout, r=r,
                group=_GROUP, ok=good, seconds=dt,
                ns_per_row=dt / nout * 1e9,
                useful_gbps=nout * r * 4 / dt / 1e9)


def smoke(r: int = 16) -> list[dict]:
    """Both forms at small shapes (the gate.sh step); a form whose rows
    are wrong carries ok=False."""
    return [probe_xla_take(512, 256, r),
            probe_xla_grouped_take(509, 256, r)]
