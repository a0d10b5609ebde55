"""Driver entry points (`__graft_entry__.py`) — the artifacts the
driver actually runs.  Round 3 shipped a broken flagship because
nothing in the suite executed the dryrun body; now the suite runs it on
the same 8-device virtual CPU mesh the driver uses.
"""

import numpy as np


def test_entry_forward_compiles_and_runs():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    vals, idxs = jax.jit(fn)(*args)
    assert vals.shape == (32, 10) and idxs.shape == (32, 10)
    # scores must be sorted descending (top-k contract)
    v = np.asarray(vals)
    assert (np.diff(v, axis=1) <= 1e-6).all()


def test_can_run_inprocess_reads_the_configured_platform(monkeypatch):
    """The dry run stays in-process only on a CPU-configured jax with
    enough devices; anything else re-execs onto a virtual CPU mesh."""
    import sys
    import types

    import __graft_entry__ as ge

    assert ge._can_run_inprocess(8)
    assert not ge._can_run_inprocess(64)

    def no_backend_init():
        raise AssertionError("initialized a non-CPU backend to count")

    fake = types.SimpleNamespace(
        config=types.SimpleNamespace(jax_platforms="tpu,cpu"),
        devices=no_backend_init,
    )
    monkeypatch.setitem(sys.modules, "jax", fake)
    assert not ge._can_run_inprocess(8)


def test_dryrun_body_full_8_devices():
    """The complete dry run — sharded train, grouped gather,
    collectives, 2D mesh, sharded top-k — on the suite's virtual mesh."""
    import __graft_entry__ as ge

    ge._dryrun_body(8)
