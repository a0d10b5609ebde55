"""Stored instance records that hold a params key the program no longer
has (`extract_params`'s ``__retired_params__``) or a value it no longer
accepts (``__retired_values__``): ``pio deploy`` rebuilds an instance's
params from its record, every ALS record written while ``gather_dtype``
was a key holds ``"gather_dtype": "float32"``, and a model trained with
the fused kernel holds ``"solver": "fused"``."""

import json
from types import SimpleNamespace

import pytest

from predictionio_tpu.controller import ParamsError
from predictionio_tpu.controller.params import params_to_json
from predictionio_tpu.templates.ecommerce import (
    ECommAlgorithmParams, ecommerce_engine,
)
from predictionio_tpu.templates.itemsimilarity import (
    ItemSimilarityParams, itemsimilarity_engine,
)
from predictionio_tpu.templates.recommendation import (
    ALSAlgorithmParams, recommendation_engine,
)
from predictionio_tpu.templates.similarproduct import (
    SimilarALSParams, similarproduct_engine,
)

TEMPLATES = {
    "recommendation": (recommendation_engine, "als", ALSAlgorithmParams),
    "similarproduct": (similarproduct_engine, "als", SimilarALSParams),
    "ecommerce": (ecommerce_engine, "ecomm", ECommAlgorithmParams),
}
# templates that had a solver but never a gather dtype
SOLVER_TEMPLATES = {
    **TEMPLATES,
    "itemsimilarity": (itemsimilarity_engine, "cosine",
                       ItemSimilarityParams),
}


def _params_from_record(template: str, **stored):
    """The algorithm params `Engine.params_from_instance` rebuilds from
    a record of the template's default params plus ``stored``."""
    factory, name, params_class = SOLVER_TEMPLATES[template]
    record = SimpleNamespace(
        data_source_params="", preparator_params="", serving_params="",
        algorithms_params=json.dumps(
            [{name: {**params_to_json(params_class()), **stored}}]),
    )
    return factory().params_from_instance(record).algorithms[0][1]


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_a_record_holding_the_float32_gather_builds_its_params(template):
    params_class = TEMPLATES[template][2]
    assert _params_from_record(template, gather_dtype="float32") \
        == params_class()


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_a_record_holding_a_bfloat16_gather_is_refused_by_name(template):
    with pytest.raises(ParamsError, match="'gather_dtype' was removed"):
        _params_from_record(template, gather_dtype="bfloat16")


@pytest.mark.parametrize("template", sorted(SOLVER_TEMPLATES))
def test_a_record_trained_with_the_fused_solver_takes_the_default_route(
        template):
    params_class = SOLVER_TEMPLATES[template][2]
    assert _params_from_record(template, solver="fused") \
        == params_class(solver="auto")
    # the values that stayed are read as they stand
    assert _params_from_record(template, solver="xla").solver == "xla"
