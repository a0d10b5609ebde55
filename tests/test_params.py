"""Params extraction tests (reference `WorkflowUtils.extractParams`)."""

from dataclasses import dataclass, field
from typing import Optional

import pytest

from predictionio_tpu.controller import Params, ParamsError, extract_params


@dataclass(frozen=True)
class Inner(Params):
    x: int = 1


@dataclass(frozen=True)
class AlgoParams(Params):
    rank: int = 10
    num_iterations: int = 20
    lam: float = 0.01
    seed: Optional[int] = None
    name: str = "als"
    flags: list[str] = field(default_factory=list)
    inner: Inner = field(default_factory=Inner)


def test_defaults():
    p = extract_params(AlgoParams, None)
    assert p.rank == 10 and p.lam == 0.01 and p.inner.x == 1


def test_values_and_coercion():
    p = extract_params(
        AlgoParams,
        {"rank": 64, "lam": 1, "seed": 3, "flags": ["a"], "inner": {"x": 5}},
    )
    assert p.rank == 64
    assert p.lam == 1.0 and isinstance(p.lam, float)
    assert p.seed == 3
    assert p.flags == ["a"]
    assert p.inner == Inner(x=5)


def test_unknown_key_rejected():
    with pytest.raises(ParamsError, match="unknown key"):
        extract_params(AlgoParams, {"rnak": 64})


def test_missing_required():
    @dataclass(frozen=True)
    class Req(Params):
        must: int

    with pytest.raises(ParamsError, match="missing required"):
        extract_params(Req, {})
    assert extract_params(Req, {"must": 2}).must == 2


def test_type_errors():
    with pytest.raises(ParamsError):
        extract_params(AlgoParams, {"rank": "ten"})
    with pytest.raises(ParamsError):
        extract_params(AlgoParams, {"rank": 1.5})
    with pytest.raises(ParamsError):
        extract_params(AlgoParams, {"name": 3})


def test_optional_none():
    assert extract_params(AlgoParams, {"seed": None}).seed is None


def test_pep604_union_validated():
    @dataclass(frozen=True)
    class New(Params):
        seed: int | None = None

    assert extract_params(New, {"seed": 3}).seed == 3
    assert extract_params(New, {"seed": None}).seed is None
    with pytest.raises(ParamsError):
        extract_params(New, {"seed": "hello"})


def test_float_rejects_non_numeric():
    with pytest.raises(ParamsError):
        extract_params(AlgoParams, {"lam": "not-a-number"})
    with pytest.raises(ParamsError):
        extract_params(AlgoParams, {"lam": True})


def test_camel_case_and_acronym_keys():
    @dataclass(frozen=True)
    class Cfg(Params):
        num_iterations: int = 1
        app_url: str = ""

    p = extract_params(Cfg, {"numIterations": 5, "appURL": "http://x"})
    assert p.num_iterations == 5
    assert p.app_url == "http://x"


def test_non_dataclass_params_class_raises_params_error():
    class Plain:
        pass

    with pytest.raises(ParamsError, match="not a params dataclass"):
        extract_params(Plain, {"x": 1})


def test_retired_key_is_dropped_at_its_old_value_in_either_spelling():
    @dataclass(frozen=True)
    class Cfg(Params):
        __retired_params__ = {"old_knob": "same"}

        rank: int = 1

    assert extract_params(Cfg, {"old_knob": "same", "rank": 2}) == Cfg(2)
    assert extract_params(Cfg, {"oldKnob": "same"}) == Cfg()
    with pytest.raises(ParamsError, match="'old_knob' was removed"):
        extract_params(Cfg, {"oldKnob": "other"})
    # a retired key is no field: it is refused where it is not declared
    with pytest.raises(ParamsError, match="unknown key"):
        extract_params(AlgoParams, {"old_knob": "same"})


def test_retired_value_of_a_kept_key_reads_as_its_successor():
    @dataclass(frozen=True)
    class Cfg(Params):
        __retired_values__ = {"mode": {"old": "new"}}

        mode: str = "new"
        rank: int = 1

    assert extract_params(Cfg, {"mode": "old", "rank": 2}) == Cfg("new", 2)
    assert extract_params(Cfg, {"mode": "other"}) == Cfg("other")
    # a value that is no string is left to the field's own conversion
    with pytest.raises(ParamsError):
        extract_params(Cfg, {"mode": ["old"]})
