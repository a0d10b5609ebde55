"""Fixture zoo: id-tagged fake controllers for pipeline tests
(reference `core/src/test/scala/io/prediction/controller/SampleEngine.scala`).

Data flowing through is tagged with the ids of every component that touched
it, so tests assert pipelines structurally.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass

from predictionio_tpu.controller import (
    Algorithm,
    DataSource,
    ModelPlacement,
    Params,
    Preparator,
    SanityCheck,
    Serving,
)


@dataclass(frozen=True)
class IdParams(Params):
    id: int = 0
    error: bool = False


@dataclass
class TrainingData(SanityCheck):
    id: int
    error: bool = False

    def sanity_check(self):
        if self.error:
            raise ValueError(f"TrainingData {self.id} is dirty")


@dataclass
class EvalInfo:
    id: int


@dataclass
class ProcessedData(SanityCheck):
    id: int
    td: TrainingData = None
    error: bool = False

    def sanity_check(self):
        if self.error:
            raise ValueError(f"ProcessedData {self.id} is dirty")


@dataclass
class FakeModel(SanityCheck):
    algo_id: int
    pd: ProcessedData = None
    error: bool = False

    def sanity_check(self):
        if self.error:
            raise ValueError(f"Model of algo {self.algo_id} is dirty")


@dataclass(frozen=True)
class Query:
    id: int


@dataclass(frozen=True)
class Prediction:
    algo_id: int
    query: Query
    served_by: int = -1


@dataclass(frozen=True)
class Actual:
    id: int


class DataSource0(DataSource):
    params_class = IdParams

    def read_training(self, ctx):
        p = self.params if isinstance(self.params, IdParams) else IdParams()
        return TrainingData(id=p.id, error=p.error)

    def read_eval(self, ctx):
        p = self.params if isinstance(self.params, IdParams) else IdParams()
        # two eval sets, each with 3 (query, actual) pairs
        return [
            (
                TrainingData(id=p.id),
                EvalInfo(id=s),
                [(Query(id=10 * s + i), Actual(id=10 * s + i)) for i in range(3)],
            )
            for s in range(2)
        ]


class Preparator0(Preparator):
    params_class = IdParams

    def prepare(self, ctx, td):
        p = self.params if isinstance(self.params, IdParams) else IdParams()
        return ProcessedData(id=p.id, td=td, error=p.error)


class Algo0(Algorithm):
    params_class = IdParams
    placement = ModelPlacement.HOST

    def train(self, ctx, pd):
        p = self.params if isinstance(self.params, IdParams) else IdParams()
        return FakeModel(algo_id=p.id, pd=pd, error=p.error)

    def predict(self, model, query):
        return Prediction(algo_id=model.algo_id, query=query)


class Algo1(Algo0):
    pass


class NonPersistingAlgo(Algo0):
    """PAlgorithm-without-PersistentModel analogue: deploy must retrain."""

    @property
    def persist_model(self) -> bool:
        return False


class Serving0(Serving):
    params_class = IdParams

    def serve(self, query, predictions):
        p = self.params if isinstance(self.params, IdParams) else IdParams()
        first = predictions[0]
        return Prediction(algo_id=first.algo_id, query=query, served_by=p.id)


class ParamsKeyFactory:
    """EngineFactory with named EngineParams presets, for
    --engine-params-key tests (reference EngineFactory.engineParams)."""

    def apply(self):
        from predictionio_tpu.controller import Engine, FirstServing
        from predictionio_tpu.controller.base import IdentityPreparator

        return Engine(
            DataSource0, IdentityPreparator, {"algo": Algo0}, FirstServing
        )

    def engine_params(self, key: str):
        from predictionio_tpu.controller.engine import EngineParams

        presets = {
            "small": EngineParams(
                data_source=("", IdParams(id=1)),
                algorithms=[("algo", IdParams(id=11))],
            ),
        }
        if key not in presets:
            raise KeyError(key)
        return presets[key]


class ToyEventStore:
    """Third-party event-store backend for the pluggable-registry test:
    loaded purely from a dotted PIO_STORAGE_SOURCES_<N>_TYPE env value
    (registry._load_custom), never imported by framework code.  Wraps
    the in-memory store and records the config it was constructed with
    — the ``Backend(conf)`` constructor contract."""

    def __init__(self, conf):
        from predictionio_tpu.storage.levents import MemoryEventStore

        self.conf = dict(conf)
        self._inner = MemoryEventStore()

    def __getattr__(self, name):
        return getattr(self._inner, name)


class ExplodingStore:
    def __init__(self, conf):
        raise ValueError("boom from backend constructor")


class FakeChip:
    """A batch function over a device with ONE queue, for the batcher's
    launch-ahead tests.  A call books ``pio.turn.prepare`` (a sleep of
    ``prepare_s``), ``pio.turn.dispatch`` (enqueues ``device_s`` of work
    behind whatever is queued), ``pio.turn.fetch`` (sleeps until its
    work is done) and ``pio.turn.decode`` (``decode_s``), and answers
    ``("ok", x)`` per item; with ``dispatch=False`` it books nothing and
    sleeps through the same time.  ``slow`` maps an item to seconds its
    batch takes on the device beyond ``device_s``.  ``fail`` maps an item to ``(stage,
    exception class)``, raised in that stage of every call holding it.
    ``calls`` keeps per call its turn's number, its items, its thread
    and the stamps ``t_enter``, ``t_sent`` (inside ``dispatch``) and
    ``t_back``."""

    def __init__(self, device_s: float, prepare_s: float = 0.002,
                 decode_s: float = 0.001, dispatch: bool = True,
                 slow: dict | None = None, fail: dict | None = None):
        self.device_s, self.prepare_s = device_s, prepare_s
        self.decode_s, self.dispatch = decode_s, dispatch
        self.slow, self.fail = slow or {}, fail or {}
        self.calls: list[dict] = []
        self._lock = threading.Lock()
        self._free_at = 0.0

    def _fault(self, xs, stage: str) -> None:
        for x in xs:
            hit = self.fail.get(x)
            if hit is not None and hit[0] == stage:
                raise hit[1](f"{x!r} fails in {stage}")

    def __call__(self, xs):
        from predictionio_tpu.obs.timeline import annotate, current_timeline

        turn = current_timeline()
        call = {"turn": getattr(turn, "turn", None), "items": list(xs),
                "thread": threading.get_ident(),
                "t_enter": time.perf_counter()}
        self.calls.append(call)
        if not self.dispatch:
            time.sleep(self.prepare_s + self.device_s + self.decode_s)
            return [("ok", x) for x in xs]
        with annotate("pio.turn.prepare"):
            time.sleep(self.prepare_s)
            self._fault(xs, "prepare")
        with annotate("pio.turn.dispatch"):
            work = self.device_s + sum(self.slow.get(x, 0.0) for x in xs)
            with self._lock:
                done = max(time.perf_counter(), self._free_at) + work
                self._free_at = done
            call["t_sent"] = time.perf_counter()
        with annotate("pio.turn.fetch"):
            time.sleep(max(done - time.perf_counter(), 0.0))
            self._fault(xs, "fetch")
        call["t_back"] = time.perf_counter()
        with annotate("pio.turn.decode"):
            time.sleep(self.decode_s)
            self._fault(xs, "decode")
        return [("ok", x) for x in xs]
