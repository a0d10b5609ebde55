"""Device-mesh parallelism utilities (the Spark-substrate replacement)."""

from .coded import (
    ParityExhausted,
    ShardHealth,
    build_coded_gather,
    build_parity_fn,
)
from .collectives import (
    all_gather_blocks,
    all_reduce_sum,
    reduce_scatter_sum,
    ring_shift,
)
from .ingest import (
    find_columnar_sharded,
    gather_ratings,
    ids_exchange,
    read_ratings_distributed,
)
from .mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    data_sharding,
    distributed_init,
    describe_devices,
    enable_compilation_cache,
    make_mesh,
    pad_to_multiple,
    replicated,
)

__all__ = [
    "ParityExhausted",
    "ShardHealth",
    "build_coded_gather",
    "build_parity_fn",
    "all_gather_blocks",
    "all_reduce_sum",
    "reduce_scatter_sum",
    "ring_shift",
    "find_columnar_sharded",
    "gather_ratings",
    "ids_exchange",
    "read_ratings_distributed",
    "DATA_AXIS",
    "MODEL_AXIS",
    "data_sharding",
    "distributed_init",
    "describe_devices",
    "enable_compilation_cache",
    "make_mesh",
    "pad_to_multiple",
    "replicated",
]
