"""pio-pilot controller unit suite: SPRT verdicts on seeded Bernoulli
streams, the min-samples floor, guardrail vetoes (burn-rate freeze,
breaker, error ratio), bounded ramp steps, and the minimal-move
property of weight updates under the sticky experiment assignment."""

from __future__ import annotations

import math

import numpy as np
import pytest

from predictionio_tpu.tenancy.autopilot import (
    STATE_COLLECTING,
    STATE_CONCLUDED,
    STATE_FROZEN,
    STATE_RAMPING,
    AutoPilot,
    AutopilotConfig,
    sprt_llr,
    sprt_test,
    step_weights,
)
from predictionio_tpu.tenancy.experiment import Experiment


# -- SPRT math ---------------------------------------------------------------


def _stream_counts(rng, n, p):
    return int(np.sum(rng.random(n) < p))


def test_sprt_accepts_h1_on_seeded_lift():
    rng = np.random.default_rng(7)
    p0 = 0.10
    c = _stream_counts(rng, 2000, 0.15)  # a real 50% lift
    res = sprt_test(2000, c, p0, p0 * 1.2, alpha=0.05, beta=0.20)
    assert res.decision == "accept_h1"
    assert res.llr >= res.upper == pytest.approx(
        math.log(0.8 / 0.05)
    )


def test_sprt_accepts_h0_when_no_lift():
    rng = np.random.default_rng(8)
    p0 = 0.10
    c = _stream_counts(rng, 2000, 0.10)  # null is true
    res = sprt_test(2000, c, p0, p0 * 1.2, alpha=0.05, beta=0.20)
    assert res.decision == "accept_h0"
    assert res.llr <= res.lower == pytest.approx(
        math.log(0.20 / 0.95)
    )


def test_sprt_continues_on_short_ambiguous_stream():
    # 3/30 at p0=0.10 sits squarely between the thresholds
    res = sprt_test(30, 3, 0.10, 0.12)
    assert res.decision == "continue"
    assert res.lower < res.llr < res.upper


def test_sprt_llr_matches_closed_form():
    n, c, p0, p1 = 100, 17, 0.1, 0.13
    ref = c * math.log(p1 / p0) + (n - c) * math.log(
        (1 - p1) / (1 - p0)
    )
    assert sprt_llr(n, c, p0, p1) == pytest.approx(ref, rel=1e-12)
    # degenerate probabilities clamp instead of blowing up
    assert math.isfinite(sprt_llr(10, 10, 0.0, 1.0))


# -- step_weights ------------------------------------------------------------


def test_step_weights_bounded_and_floor():
    w = {"a": 0.5, "b": 0.5}
    w1 = step_weights(w, "a", max_step=0.1, min_weight=0.05)
    assert w1 == {"a": 0.6, "b": 0.4}
    for _ in range(10):
        w1 = step_weights(w1, "a", max_step=0.1, min_weight=0.05)
    assert w1["b"] == pytest.approx(0.05)  # floored, never zeroed
    assert w1["a"] == pytest.approx(0.95)
    # nothing left to move: unchanged dict comes back
    assert step_weights(w1, "a", 0.1, 0.05) == w1


def test_step_weights_only_from_restricts_donors():
    w = {"a": 0.4, "b": 0.3, "c": 0.3}
    w1 = step_weights(w, "a", max_step=0.1, min_weight=0.05,
                      only_from={"c"})
    assert w1["b"] == pytest.approx(0.3)  # untouched
    assert w1["c"] == pytest.approx(0.2)
    assert w1["a"] == pytest.approx(0.5)
    assert sum(w1.values()) == pytest.approx(1.0)


def test_weight_update_minimal_move_under_sticky_assignment():
    """One bounded step re-assigns roughly |w - w'| of users and
    NOBODY moves against the ramp direction (the Experiment interval
    layout contract the autopilot leans on)."""
    exp = Experiment("app", {"a": 0.5, "b": 0.5}, salt="s")
    users = [f"u{n}" for n in range(4000)]
    before = {u: exp.assign(u) for u in users}
    exp.set_weights(step_weights(exp.weights(), "b", 0.1, 0.05))
    after = {u: exp.assign(u) for u in users}
    moved = [u for u in users if before[u] != after[u]]
    assert all(
        before[u] == "a" and after[u] == "b" for u in moved
    )
    frac = len(moved) / len(users)
    assert 0.05 < frac < 0.15  # ~0.1 of traffic, hash noise aside


# -- the controller over a stub registry -------------------------------------


class _Breaker:
    def __init__(self, state="closed"):
        self.state = state


class _Runtime:
    def __init__(self, state="closed"):
        self.breaker = _Breaker(state)


class _OnlineStub:
    def __init__(self, stats):
        self.stats = stats

    def snapshot(self):
        return self.stats


class _RegistryStub:
    """The slice of TenantRegistry the controller reads."""

    def __init__(self, weights, stats, breakers=()):
        self._exps = {
            app: Experiment(app, dict(w), salt="t")
            for app, w in weights.items()
        }
        self.online = _OnlineStub(stats)
        self._runtimes = {
            key: _Runtime(state) for key, state in dict(breakers).items()
        }
        self.applied: list[tuple[str, dict]] = []

    def apps(self):
        return sorted(self._exps)

    def experiment(self, app):
        return self._exps[app]

    def set_weights(self, app, weights):
        self.applied.append((app, dict(weights)))
        self._exps[app].set_weights(weights)


def _stats(app, **rates):
    out = {}
    for variant, (n, c) in rates.items():
        out[f"{app}/{variant}"] = {
            "impressions": n, "conversions": c,
            "rate": c / n if n else 0.0,
        }
    return out


CFG = AutopilotConfig(min_samples=50, max_step=0.1, min_weight=0.05)


def _pilot(reg, tmp_path, cfg=CFG, **kw):
    # the default guardrail input is the process-wide pio_slo_burn_rate
    # gauge, which any server test of the same worker may have left
    # burning: a test that is not about the guardrail reads a calm one
    kw.setdefault("burn_rate_fn", lambda: 0.0)
    return AutoPilot(reg, config=cfg, manifest_id="t-pilot", **kw)


@pytest.fixture(autouse=True)
def _runlog_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_TPU_RUNLOG_DIR", str(tmp_path / "runs"))


def test_min_samples_floor_holds(tmp_path):
    reg = _RegistryStub(
        {"app": {"a": 0.5, "b": 0.5}},
        _stats("app", a=(30, 20), b=(30, 1)),  # huge gap, tiny n
    )
    pilot = _pilot(reg, tmp_path)
    pilot.tick()
    assert reg.applied == []  # no ramp off ten lucky conversions
    cell = pilot.payload()["apps"]["app"]
    assert cell["state"] == STATE_COLLECTING
    assert cell["last"]["reason"] == "min_samples"


def test_ramp_steps_bounded_until_concluded(tmp_path):
    reg = _RegistryStub(
        {"app": {"a": 0.5, "b": 0.5}},
        _stats("app", a=(400, 40), b=(400, 120)),  # b lifts 3x
    )
    pilot = _pilot(reg, tmp_path)
    prev = reg.experiment("app").weights()
    for _ in range(12):
        pilot.tick()
        cur = reg.experiment("app").weights()
        assert abs(cur["b"] - prev["b"]) <= CFG.max_step + 1e-9
        prev = cur
        if pilot.payload()["apps"]["app"]["state"] == STATE_CONCLUDED:
            break
    assert pilot.payload()["apps"]["app"]["state"] == STATE_CONCLUDED
    assert prev["b"] == pytest.approx(0.95)
    assert prev["a"] == pytest.approx(CFG.min_weight)  # never zeroed
    decisions = [
        d["decision"]
        for d in pilot.payload()["apps"]["app"]["decisions"]
    ]
    assert decisions.count("ramp") == len(reg.applied) == 5
    assert decisions[-1] == "conclude"


def test_no_lift_holds_without_moving_traffic(tmp_path):
    reg = _RegistryStub(
        {"app": {"a": 0.5, "b": 0.5}},
        _stats("app", a=(2000, 200), b=(2000, 201)),
    )
    pilot = _pilot(reg, tmp_path)
    pilot.tick()
    assert reg.applied == []
    assert (pilot.payload()["apps"]["app"]["last"]["reason"]
            == "no_lift")


def test_burn_rate_breach_freezes_ramping(tmp_path):
    reg = _RegistryStub(
        {"app": {"a": 0.5, "b": 0.5}},
        _stats("app", a=(400, 40), b=(400, 120)),
    )
    burn = {"v": 9.0}
    pilot = _pilot(reg, tmp_path, burn_rate_fn=lambda: burn["v"])
    pilot.tick()
    cell = pilot.payload()["apps"]["app"]
    assert cell["state"] == STATE_FROZEN
    assert cell["last"]["reason"] == "burn_rate"
    assert reg.applied == []  # a winner exists, traffic did NOT move
    # the breach clears -> ramping resumes on the next tick
    burn["v"] = 0.0
    pilot.tick()
    assert pilot.payload()["apps"]["app"]["state"] == STATE_RAMPING
    assert len(reg.applied) == 1


def test_breaker_veto_ramps_broken_variant_down(tmp_path):
    # "b" converts best but its breaker is open: it must be ramped
    # DOWN, toward the best eligible variant
    reg = _RegistryStub(
        {"app": {"a": 0.5, "b": 0.5}},
        _stats("app", a=(400, 40), b=(400, 120)),
        breakers={("app", "b"): "open"},
    )
    pilot = _pilot(reg, tmp_path)
    for _ in range(8):
        pilot.tick()
    w = reg.experiment("app").weights()
    assert w["b"] == pytest.approx(CFG.min_weight)
    assert w["a"] == pytest.approx(0.95)
    vetoes = [
        d for d in pilot.payload()["apps"]["app"]["decisions"]
        if d["decision"] == "veto"
    ]
    assert vetoes and all(
        "breaker_open" in d["reason"] for d in vetoes
    )
    # with only one eligible variant left, SPRT cannot run: hold
    assert (pilot.payload()["apps"]["app"]["last"]["reason"]
            == "single_variant")


def test_error_ratio_veto(tmp_path):
    from predictionio_tpu.obs import TENANT_QUERIES_TOTAL

    reg = _RegistryStub(
        {"eapp": {"a": 0.5, "b": 0.5}},
        _stats("eapp", a=(400, 40), b=(400, 120)),
    )
    TENANT_QUERIES_TOTAL.labels(
        app="eapp", variant="b", status="error"
    ).inc(30)
    TENANT_QUERIES_TOTAL.labels(
        app="eapp", variant="b", status="ok"
    ).inc(10)
    pilot = _pilot(reg, tmp_path)
    pilot.tick()
    last = pilot.payload()["apps"]["eapp"]["last"]
    assert last["decision"] == "veto"
    assert "b:error_ratio" in last["reason"]


def test_tick_never_raises_and_writes_manifest(tmp_path):
    from predictionio_tpu.obs.runlog import read_manifest, runs_root

    reg = _RegistryStub(
        {"app": {"a": 0.5, "b": 0.5}},
        _stats("app", a=(400, 40), b=(400, 120)),
    )

    def broken_apply(app, weights):
        raise RuntimeError("weight endpoint down")

    pilot = _pilot(reg, tmp_path, apply_weights=broken_apply)
    pilot.tick()  # must not raise
    pilot.close()
    view = read_manifest(runs_root() / "t-pilot")
    events = [e for e in view["events"]
              if e.get("event") == "decision"]
    assert events and events[-1]["decision"] == "ramp"
    assert events[-1]["llr"] >= events[-1]["upper"]
    assert view["final"]["status"] == "completed"
