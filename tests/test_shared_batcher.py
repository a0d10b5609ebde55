"""pio-confluence: the shared continuous batcher's fairness contract.

One SharedBatcher serves every tenant on a server; these tests pin the
properties the hive depends on:

* **Starvation-freedom** — a tenant flooding the shared queue cannot
  starve a well-behaved sibling: the WDRR claim gives the sibling its
  weighted share of every dispatcher turn, so its entries complete
  within the first claims, not after the flood drains.
* **Weight fidelity** — deficit weights split a claim proportionally,
  and a hot ``POST /tenants/weights`` update (registry
  ``set_weights`` → ``deficit_weight`` → the view's pull-style
  ``weight_fn``) reshapes the very next claim with no push plumbing.
* **Accounting identity** — the pulse timeline's "segments sum exactly
  to covered wall time" invariant survives mixed-tenant batches and
  multi-group execution turns.
* **Blast radius** — one tenant's failing batch_fn fails only its own
  entries; co-claimed entries of other tenants complete normally.

The claim-policy tests drive ``_claim_locked`` directly on a
dispatcher-less batcher (entries staged by hand under the condition
variable) so the claim composition is deterministic — no sleeps, no
thread races deciding what a "round" contains.
"""

import threading
import time

import pytest

from predictionio_tpu.server.microbatch import (
    SharedBatcher,
    SharedBatcherView,
    _Entry,
)


def _stage(sb, tenant, fn, items):
    """Stage entries directly into the pending queue (bypassing the
    dispatcher) so a claim's composition is a pure function of the
    queue, not of thread timing."""
    with sb._cond:
        for it in items:
            sb._pending.append(_Entry(it, tenant=tenant, fn=fn))


def _claim(sb):
    with sb._cond:
        return sb._claim_locked()


def _ident(xs):
    return list(xs)


# -- claim policy ----------------------------------------------------------


def test_flooding_tenant_cannot_starve_sibling():
    """100 queued entries from whale tenant A vs 4 from sibling B at
    equal weights: EVERY claim of 8 gives B its half until B drains —
    B's last entry leaves in claim 1, not claim 13."""
    sb = SharedBatcher(max_batch=8)
    _stage(sb, "A", _ident, range(100))
    _stage(sb, "B", _ident, [f"b{i}" for i in range(4)])
    first = _claim(sb)
    assert len(first) == 8
    by = {}
    for e in first:
        by.setdefault(e.tenant, []).append(e.item)
    # equal weights: the claim splits 4/4 and B is fully served in the
    # FIRST dispatcher turn despite 25x queue imbalance
    assert by["B"] == ["b0", "b1", "b2", "b3"]
    assert len(by["A"]) == 4
    # and B's FIFO order within the claim is preserved
    sb.close()


def test_single_tenant_claim_rides_fifo_fast_path():
    """A solo-tenant queue claims exactly like the base batcher (FIFO
    prefix), with zero WDRR bookkeeping."""
    sb = SharedBatcher(max_batch=4)
    _stage(sb, "A", _ident, range(10))
    batch = _claim(sb)
    assert [e.item for e in batch] == [0, 1, 2, 3]
    assert sb.mixed_batches == 0
    assert sb.tenant_claims == {"A": 4}
    sb.close()


def test_weighted_claims_split_proportionally():
    """Weights 3:1 over deep queues: a claim of 8 takes ~6 from the
    heavy tenant and ~2 from the light one — and the light one still
    ALWAYS gets its floor share (never zero)."""
    sb = SharedBatcher(max_batch=8)
    sb.set_weights({"heavy": 3.0, "light": 1.0})
    _stage(sb, "heavy", _ident, range(50))
    _stage(sb, "light", _ident, range(50))
    batch = _claim(sb)
    n_heavy = sum(1 for e in batch if e.tenant == "heavy")
    n_light = sum(1 for e in batch if e.tenant == "light")
    assert n_heavy + n_light == 8
    assert n_heavy == 6
    assert n_light == 2
    sb.close()


def test_zero_weight_tenant_still_drains():
    """The MIN_SHARE floor: even a weight-0 tenant accrues deficit and
    cannot be starved out of the queue forever."""
    sb = SharedBatcher(max_batch=4)
    sb.set_weights({"whale": 1.0, "zero": 0.0})
    _stage(sb, "whale", _ident, range(1000))
    _stage(sb, "zero", _ident, ["z"])
    # 1/MIN_SHARE rounds bound the accrual: the zero-weight tenant's
    # single entry must leave within a handful of claims
    for _ in range(30):
        batch = _claim(sb)
        if any(e.tenant == "zero" for e in batch):
            break
    else:
        pytest.fail("zero-weight tenant starved across 30 claims")
    sb.close()


def test_weighted_claims_split_3_to_1_across_two_leaders():
    """The WDRR claim under launch-ahead turns: with a device-bound batch
    function two leaders take turns claiming, each claim still under the
    one lock, and every claim of 8 from deep queues weighted 3:1 takes 6
    and 2."""
    from fixtures import FakeChip

    chip = FakeChip(0.02, prepare_s=0.002)
    sb = SharedBatcher(max_batch=8, pad_batches=False)
    vh = SharedBatcherView(sb, "heavy", chip, weight=3.0)
    vl = SharedBatcherView(sb, "light", chip, weight=1.0)
    out, ev, cb_for = _collector(80)
    with sb._cond:    # every entry is pending before the first claim
        for i in range(60):
            vh.submit_nowait(("heavy", i), cb_for(("heavy", i)))
        for i in range(20):
            vl.submit_nowait(("light", i), cb_for(("light", i)))
    assert ev.wait(20)
    assert all(out[k] == (("ok", k), None) for k in out)
    splits = [(sum(1 for t, _ in c["items"] if t == "heavy"),
               sum(1 for t, _ in c["items"] if t == "light"))
              for c in chip.calls]
    assert splits == [(6, 2)] * 10
    assert len({c["turn"] for c in chip.calls}) == 10
    # two leaders, the next claimed while the last was on the device
    assert len({c["thread"] for c in chip.calls}) == 2
    assert any(b["t_enter"] < a["t_back"]
               for a, b in zip(chip.calls, chip.calls[1:]))
    vh.close(); vl.close(); sb.close()


def test_hot_weight_update_reshapes_next_claim():
    """Flip the weights between claims: the split flips with them —
    the live-reconfiguration contract behind POST /tenants/weights."""
    sb = SharedBatcher(max_batch=8)
    sb.set_weights({"a": 3.0, "b": 1.0})
    _stage(sb, "a", _ident, range(100))
    _stage(sb, "b", _ident, range(100))
    first = _claim(sb)
    assert sum(1 for e in first if e.tenant == "a") == 6
    sb.set_weights({"a": 1.0, "b": 3.0})
    # drain leftover deficit effects across one transition claim, then
    # the steady-state split must match the NEW weights
    _claim(sb)
    nxt = _claim(sb)
    assert sum(1 for e in nxt if e.tenant == "b") >= 5
    sb.close()


def test_weight_fn_pull_beats_cached_weight():
    """A view's weight_fn is consulted at claim time and overrides the
    registration-time weight — the pull path the serving layer wires
    to ``TenantRegistry.deficit_weight``."""
    sb = SharedBatcher(max_batch=8)
    live = {"a": 3.0}
    sb.register_tenant("a", weight=1.0, weight_fn=lambda: live["a"])
    sb.register_tenant("b", weight=1.0)
    _stage(sb, "a", _ident, range(100))
    _stage(sb, "b", _ident, range(100))
    batch = _claim(sb)
    assert sum(1 for e in batch if e.tenant == "a") == 6
    live["a"] = 1.0
    _claim(sb)
    nxt = _claim(sb)
    assert sum(1 for e in nxt if e.tenant == "a") == 4
    sb.close()


def test_registry_deficit_weight_follows_hot_update():
    """The registry half of the chain: ``deficit_weight`` is the app-
    normalized variant weight and tracks ``set_weights`` (the admin
    API / router-broadcast primitive) immediately."""
    from predictionio_tpu.tenancy.registry import (
        TenantRegistry, TenantSpec,
    )

    specs = [
        TenantSpec("app0", "control", engine_json="x.json", weight=9.0),
        TenantSpec("app0", "treatment", engine_json="x.json", weight=1.0),
        TenantSpec("app1", "main", engine_json="x.json"),
    ]
    reg = TenantRegistry(specs)
    assert reg.deficit_weight(("app0", "control")) == pytest.approx(0.9)
    assert reg.deficit_weight(("app0", "treatment")) == pytest.approx(0.1)
    # a single-variant app weighs its whole app share
    assert reg.deficit_weight(("app1", "main")) == pytest.approx(1.0)
    # unknown tenants never weigh 0 (a scheduling lookup must not shed)
    assert reg.deficit_weight(("nope", "x")) == 1.0
    reg.set_weights("app0", {"control": 1.0, "treatment": 3.0})
    assert reg.deficit_weight(("app0", "control")) == pytest.approx(0.25)
    assert reg.deficit_weight(("app0", "treatment")) == pytest.approx(0.75)
    reg.close()


def test_retire_keeps_state_across_reload_overlap():
    """A reload registers the NEW view before closing the old one; the
    overlapping retire must not clobber the fresh registration."""
    sb = SharedBatcher(max_batch=4)
    v_old = SharedBatcherView(sb, "t", _ident)
    v_new = SharedBatcherView(sb, "t", _ident)  # reload's fresh view
    v_old.close()  # old view retires AFTER the new one registered
    with sb._cond:
        assert sb._reg_counts.get("t") == 1
        assert "t" in sb._rr
    v_new.close()
    with sb._cond:
        assert "t" not in sb._reg_counts
        assert "t" not in sb._rr
    sb.close()


# -- execution: grouping, isolation, timelines -----------------------------


def _collector(n):
    """Callback factory for the continuous path: results keyed by the
    caller's tag, an Event set when the n-th callback lands.  The
    dispatcher fires callbacks sequentially on its own thread, so the
    callbacks themselves must never block on each other."""
    results = {}
    ev = threading.Event()

    def cb_for(key):
        def cb(entry):
            results[key] = (entry.value, entry.error)
            if len(results) >= n:
                ev.set()
        return cb

    return results, ev, cb_for


def test_mixed_claim_groups_by_fn_and_both_complete():
    """Two tenants with DIFFERENT models in one claim: each group runs
    its own batch_fn, every entry gets its own tenant's result."""
    sb = SharedBatcher(max_batch=8)
    seen = {"a": [], "b": []}

    def fn_a(xs):
        seen["a"].append(len(xs))
        return [("a", x) for x in xs]

    def fn_b(xs):
        seen["b"].append(len(xs))
        return [("b", x) for x in xs]

    va = SharedBatcherView(sb, "a", fn_a)
    vb = SharedBatcherView(sb, "b", fn_b)
    results, ev, cb_for = _collector(4)

    # stall the dispatcher briefly so all four entries land in ONE
    # claim (the dispatcher claims whatever is pending when it wakes)
    with sb._cond:
        va.submit_nowait(1, cb_for("a1"))
        va.submit_nowait(2, cb_for("a2"))
        vb.submit_nowait(3, cb_for("b1"))
        vb.submit_nowait(4, cb_for("b2"))
    assert ev.wait(10)
    assert results["a1"] == (("a", 1), None)
    assert results["a2"] == (("a", 2), None)
    assert results["b1"] == (("b", 3), None)
    assert results["b2"] == (("b", 4), None)
    # each fn saw ONE coalesced call of its two entries (pow2 pad = 2)
    assert seen["a"] == [2]
    assert seen["b"] == [2]
    assert sb.mixed_batches >= 1
    va.close(); vb.close(); sb.close()


def test_failing_tenant_fn_does_not_fail_sibling():
    """Blast radius of a broken model: tenant A's batch_fn raises; its
    entries error, tenant B's entries in the SAME claim succeed."""
    sb = SharedBatcher(max_batch=8)

    def fn_bad(xs):
        raise RuntimeError("model a is broken")

    va = SharedBatcherView(sb, "a", fn_bad)
    vb = SharedBatcherView(sb, "b", _ident)
    out, ev, cb_for = _collector(2)

    with sb._cond:
        va.submit_nowait("x", cb_for("a"))
        vb.submit_nowait("y", cb_for("b"))
    assert ev.wait(10)
    assert isinstance(out["a"][1], RuntimeError)
    assert out["b"] == ("y", None)
    va.close(); vb.close(); sb.close()


def test_timeline_identity_survives_mixed_tenant_batch():
    """The pulse accounting identity — segments sum EXACTLY to covered
    wall time — holds for entries that rode a mixed-tenant,
    multi-group execution turn."""
    from predictionio_tpu.obs.timeline import Timeline

    sb = SharedBatcher(max_batch=8)

    def slow_a(xs):
        time.sleep(0.02)
        return list(xs)

    def slow_b(xs):
        time.sleep(0.01)
        return list(xs)

    va = SharedBatcherView(sb, "a", slow_a)
    vb = SharedBatcherView(sb, "b", slow_b)
    tls = {"a": Timeline("serve"), "b": Timeline("serve")}
    for tl in tls.values():
        tl.mark("parse")
    _, ev, cb_for = _collector(2)

    with sb._cond:
        va.submit_nowait(1, cb_for("a"), timeline=tls["a"])
        vb.submit_nowait(2, cb_for("b"), timeline=tls["b"])
    assert ev.wait(10)
    for name, tl in tls.items():
        segs = tl.segments
        assert {"queue_wait", "batch_wait", "device"} <= set(segs), name
        assert sum(segs.values()) == pytest.approx(
            tl._last - tl.t0, abs=1e-6
        ), name
    va.close(); vb.close(); sb.close()


def test_sibling_p99_bounded_under_flood():
    """End-to-end with the real dispatcher: tenant A floods the shared
    queue continuously; tenant B's sequential blocking submits stay
    bounded by a few dispatcher turns each — NOT by A's backlog.  With
    per-call ~2 ms and B's share of every claim, B's worst-case
    latency is orders below draining A's backlog first."""
    sb = SharedBatcher(max_batch=8)
    call_s = 0.002

    def slow(xs):
        time.sleep(call_s)
        return list(xs)

    va = SharedBatcherView(sb, "A", slow)
    vb = SharedBatcherView(sb, "B", slow)
    # A floods: 200 async entries queued up front (~50+ claims deep)
    for i in range(200):
        va.submit_nowait(i, lambda e: None)
    # B: sequential blocking submits, measured individually
    worst = 0.0
    for i in range(5):
        t0 = time.perf_counter()
        assert vb.submit(i) == i
        worst = max(worst, time.perf_counter() - t0)
    # draining A's 200 entries alone costs >= 25 claims * call_s;
    # B bounded far under that proves it rode its share of early
    # claims (generous bound: a handful of turns + scheduler noise)
    assert worst < 0.5, f"sibling p99 {worst:.3f}s under flood"
    stats = sb.stats()
    assert stats["tenantClaims"].get("B") == 5
    va.close(); vb.close(); sb.close()


def test_view_close_semantics_and_shared_stats():
    """A closed view refuses submits with the exact RuntimeError the
    reload-retry edge keys on, while the core keeps serving its other
    tenants; stats are tagged shared + per-view tenant."""
    sb = SharedBatcher(max_batch=4)
    va = SharedBatcherView(sb, "a", _ident)
    vb = SharedBatcherView(sb, "b", _ident)
    assert va.submit(1) == 1
    va.close()
    with pytest.raises(RuntimeError, match="closed"):
        va.submit(2)
    with pytest.raises(RuntimeError, match="closed"):
        va.submit_nowait(2, lambda e: None)
    # the sibling is untouched
    assert vb.submit(3) == 3
    st = vb.stats()
    assert st["shared"] is True
    assert st["tenant"] == "b"
    assert st["requests"] == 2
    vb.close(); sb.close()


def test_engine_server_shared_batcher_wiring(storage_memory):
    """The serving layer end of the chain: the anchor's batcher is a
    view on the server's ONE core; a reload swaps the view but keeps
    the core; the only other layout is no batcher at all
    (``microbatch="off"``), which builds no core."""
    from predictionio_tpu.controller.base import (
        Algorithm, DataSource, WorkflowContext,
    )
    from predictionio_tpu.controller.engine import SimpleEngine
    from predictionio_tpu.server.serving import (
        EngineServer, ServerConfig,
    )
    from predictionio_tpu.workflow.train import run_train

    class DS(DataSource):
        def read_training(self, ctx):
            return 1

    class BatchedAlgo(Algorithm):
        def train(self, ctx, data):
            return {"w": 2}

        def predict(self, model, query):
            return {"y": model["w"] * query.get("x", 0)}

        def batch_predict(self, model, queries):
            return [self.predict(model, q) for q in queries]

    ctx = WorkflowContext(storage=storage_memory)
    engine = SimpleEngine(DS, BatchedAlgo)
    ep = engine.params_from_variant({})
    iid = run_train(engine, ep, ctx=ctx)
    srv = EngineServer(engine, ep, iid, ctx=ctx,
                       config=ServerConfig(port=0))
    try:
        assert isinstance(srv.batcher, SharedBatcherView)
        assert srv.batcher.core is srv._shared_core
        assert srv.predict_json({"x": 3}) == {"y": 6}
        # reload swaps the anchor view but keeps the ONE core (and the
        # tenant's scheduling state survives the registration overlap)
        old_view = srv.batcher
        srv.reload()
        assert srv.batcher is not old_view
        assert srv.batcher.core is srv._shared_core
        with srv._shared_core._cond:
            assert srv._shared_core._reg_counts[srv.batcher.tenant] == 1
        assert srv.predict_json({"x": 5}) == {"y": 10}
    finally:
        srv.stop()
    assert srv._shared_core is None  # stop() owns the core

    srv = EngineServer(
        engine, ep, iid, ctx=ctx,
        config=ServerConfig(port=0, microbatch="off"),
    )
    try:
        assert srv.batcher is None
        assert srv._shared_core is None
        assert srv.predict_json({"x": 7}) == {"y": 14}
    finally:
        srv.stop()
