"""utils subsystem: logging tiers, debug dumper."""

import dataclasses
import logging

import numpy as np

from predictionio_tpu.utils import (
    debug_string,
    modify_logging,
    setup_logging,
)


def test_modify_logging_tiers():
    modify_logging(verbose=False)
    assert logging.getLogger().level == logging.INFO
    assert logging.getLogger("jax").level == logging.WARNING
    modify_logging(verbose=True)
    assert logging.getLogger().level == logging.DEBUG
    assert logging.getLogger("jax").level == logging.INFO
    modify_logging(verbose=False)


def test_setup_logging_installs_single_handler():
    setup_logging()
    n1 = len(logging.getLogger().handlers)
    setup_logging()
    assert len(logging.getLogger().handlers) == n1


def test_debug_string_arrays_and_nesting():
    import jax.numpy as jnp

    s = debug_string({"x": np.arange(6.0).reshape(2, 3), "y": [1, "a"]})
    assert "2x3" in s and "float64" in s and "'y': [1,'a']" in s
    s2 = debug_string(jnp.ones((4,), jnp.float32))
    assert "4" in s2 and "float32" in s2


def test_debug_string_dataclass_and_truncation():
    @dataclasses.dataclass
    class TD:
        id: int
        vals: list

    s = debug_string(TD(id=3, vals=list(range(100))))
    assert s.startswith("TD(id=3") and "..." in s
