"""Observability + debugging utilities (SURVEY §5 tracing row).

The reference's observability is grizzled-slf4j over log4j with a
verbosity switch (`workflow/WorkflowUtils.scala:277-288`) and a recursive
RDD dumper (`debugString`, `:228-245`).  Here: stdlib logging with the
same two-tier chatty/root split and a pytree-aware debug dumper for
jax/numpy data.  (Profiler captures: ``GET /debug/profile?seconds=S``,
``obs/timeline.capture_profile``.)
"""

from .debug import debug_string
from .logging import modify_logging, setup_logging

__all__ = [
    "debug_string",
    "modify_logging",
    "setup_logging",
]
