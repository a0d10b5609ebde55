"""Block ALS matrix factorization on TPU.

The TPU-native replacement for Spark MLlib's ``ALS.train`` /
``ALS.trainImplicit`` that the reference's recommendation-family templates
invoke (`/root/reference/examples/scala-parallel-recommendation/custom-query/
src/main/scala/ALSAlgorithm.scala`, similarproduct, ecommerce).  The MLlib
implementation block-partitions factors across Spark executors and shuffles
factor blocks each half-iteration (SURVEY §2.7(2)); here the whole problem is
HBM-resident and each half-iteration is ONE XLA computation:

* Host preprocessing groups rows into **power-of-two padded buckets**
  (ALX-style, arXiv 2112.02194): rows are keyed by next-pow2(rating count),
  so the device sees only static shapes.  Only the time-sorted COO arrays
  and tiny per-bucket ``(rows, starts, counts)`` vectors are transferred;
  the padded ``[B, K]`` rating blocks are expanded **on device**, once,
  by a staging program (``_expand_side``: a gather from the sorted COO),
  which cuts host->HBM traffic ~3x; the blocks stay on the device and
  the sorted columns are dropped.
* Per bucket, inside one program a half: gather opposite factors
  ``[B, K, R]`` -> masked Gram matrices via einsum (MXU) -> batched
  Cholesky solve -> masked scatter into the factor table (OOB rows from
  batch padding are dropped).
* The whole half-iteration is a single ``jit`` with the factor table
  donated, so a 20-iteration train is 40 dispatches and exactly 2 compiled
  executables (one per direction) regardless of bucket count.
* Sharding: bucket batch dims (the padded blocks' too) are sharded over
  the mesh's ``data`` axis; factor tables are replicated, so gathers are local
  and XLA inserts the collectives for the scatter from the shardings
  (no NCCL/MPI analogue needed).

Both regularization conventions are implemented:

* ``explicit``  — least squares with ALS-WR weighted-λ (λ·n_row·I), which is
  Spark MLlib 1.3's convention; RMSE-parity target per BASELINE.md.
* ``implicit``  — Hu-Koren-Volinsky confidence weighting c = 1 + α·r
  (``ALS.trainImplicit`` parity: the default of the reference templates).
"""

from __future__ import annotations

import functools
import logging
import math
import time
from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import (
    ALS_EXCHANGE_BYTES_TOTAL, ALS_GATHER_BYTES_TOTAL, ALS_GRAM_ENTRIES_TOTAL,
    ALS_SOLVE_SYSTEMS_TOTAL, ALS_WRITE_ROWS_TOTAL, TRAIN_PHASE_SECONDS,
    tower, xray,
)
from ..obs.timeline import annotate
from ..parallel.mesh import DATA_AXIS, pad_to_multiple, replicated
from ..storage.columnar import Ratings

logger = logging.getLogger(__name__)

# cap on the grouped-gather slab intermediate ([chunk, K, G, R]): the
# slab is G (8) times the row gather's output, so it's produced in
# row-chunks of at most this many bytes and shrunk back to [*, K, R] by
# the in-slab select before the next chunk materializes
_GROUPED_SLAB_BYTES = 256 * 1024 * 1024

# rows of one grouped-gather slab: the sublanes of a float32 memory tile
_GATHER_GROUP_ROWS = 8

__all__ = [
    "ALSConfig",
    "ALSFactors",
    "ALSTrainer",
    "train_als",
    "sweep_train_als",
    "rmse",
    "BucketLayout",
    "build_bucket_layout",
]

# cap on B*K entries of a single bucket chunk: bounds the [B, K, R]
# gathered intermediate (~1 GiB at rank 64, f32) regardless of dataset size,
# and a staged [B, K] block of ids or of ratings at 16 MiB
MAX_ENTRIES_PER_BUCKET = 4 << 20

# share of one device's memory that a bucket chunk's [B, R, R] float32
# Gram may take: the entry cap alone lets a K=8 chunk hold 524,288 rows,
# whose Gram at rank 128 is 34 GB
_GRAM_MEMORY_SHARE = 16


def _device_memory_bytes() -> int:
    """Bytes of one device's memory as the backend reports them
    (``bytes_limit``); a backend that keeps no statistics, as the CPU's,
    counts as 16 GiB, a v5e chip's."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 16 << 30))


def _rows_in_memory_share(row_bytes: int,
                          share: int = _GRAM_MEMORY_SHARE) -> int:
    """How many rows of ``row_bytes`` fit one ``share``-th (a sixteenth)
    of one device's memory, rounded down to a power of two, so that a
    few bytes more or less of ``bytes_limit`` stage the same shapes."""
    rows = _device_memory_bytes() // share // row_bytes
    return 1 << max(rows, 1).bit_length() - 1


def gram_chunk_rows(rank: int, n_dev: int = 1) -> int:
    """Most rows a bucket chunk may hold over ``n_dev`` devices, so that
    each device's ``[B / n_dev, R, R]`` float32 Gram stays under a
    sixteenth of its memory (16 GB: 32,768 rows a device at rank 64,
    8,192 at rank 128)."""
    return _rows_in_memory_share(4 * rank * rank) * n_dev


# share of one device's memory that a bucket chunk's gathered rows
# [B, K, R] may take under replicated placement.  A quarter: the largest
# chunk the entry cap ever allowed, rank 128 in float32, is 2.1 GB, an
# eighth of a v5e's memory, and the power of two below a quarter's rows
# keeps it (and so every shape staged at a rank of 128 or less)
_GATHER_MEMORY_SHARE = 4


def gather_chunk_entries(rank: int, n_dev: int = 1) -> int:
    """Most entries (B*K) a bucket chunk may hold under REPLICATED
    placement over ``n_dev`` devices, so that each device's gathered
    float32 rows ``[B / n_dev, K, R]`` stay under a quarter of its
    memory at ANY rank (16 GB: 262,144 entries, 2.1 GB, at rank 2,048,
    where ``MAX_ENTRIES_PER_BUCKET`` alone would gather 34 GB).  Never
    more than ``MAX_ENTRIES_PER_BUCKET``, which binds up to rank 128.  A
    row wider than the bound is a chunk of its own: a row's entries are
    not split."""
    return min(MAX_ENTRIES_PER_BUCKET,
               _rows_in_memory_share(rank * 4, _GATHER_MEMORY_SHARE)
               * n_dev)


def exchange_chunk_entries(rank: int, n_dev: int) -> int:
    """Most entries (B*K) a bucket chunk may hold under SHARDED
    placement: a device looks up every device's ids of the chunk, so it
    holds ``n_dev + 1`` times its own ``[B / n_dev, K, R]`` float32 rows
    (the partial answers and, after the reduce-scatter, its own), and
    those stay under the same sixteenth of its memory (16 GB at rank
    128: 262,144 entries a device).  Never more than
    ``MAX_ENTRIES_PER_BUCKET``."""
    return min(MAX_ENTRIES_PER_BUCKET,
               _rows_in_memory_share((n_dev + 1) * 4 * rank) * n_dev)


# pad width that marks a DENSE bucket chunk: its rows hold so large a
# share of the opposite table that their normal equations are a blocked
# matmul over that whole table (`_dense_normal_equations`), not a gather
DENSE_K = 0

# opposite rows of one block of a dense chunk: a partial Gram sums at
# most this many outer products before it is added to the others (one
# chip, PR 35: 1,024 rows against 480,189 at rank 64 take 72.3 ms in
# blocks of 4,096 and 81.1 ms in blocks of 8,192)
_DENSE_BLOCK_ROWS = 4096

# `dense_min_count`'s costs, from one v5e chip at rank 64.  A gathered
# chunk of 4,194,304 padded entries, gathered and its Grams built, takes
# 55.5 ms from the netflix table's 480,189 user rows at every pad width
# from 2,048 to 16,384 (13.2 ns an entry, 11.7 of it the gather) and
# 14.3 ms from a 17,770-row table (3.4 ns); `_gather_entry_ns` between
# them.  The dense form costs by the (row, opposite row) pair where its
# weights are whole numbers (three bf16 passes hold them): against
# 480,189 rows 929 rows in 66.6 ms, 1,859 in 126.9, 3,717 in 247.3
# (0.149, 0.142, 0.139 ns); twice that for the implicit form's float32
# weights.  So against the netflix user table a K = 4,096 row gathers in
# 54 us and would take 67 dense, a K = 8,192 one 108 against 67.
# Below ``_DENSE_MIN_COUNT`` ratings a row's whole gather is tens of
# microseconds and the block's fixed cost wins nothing, which keeps
# every small table (the CPU tests', fold-in's, a catalogue's) on the
# gathered path.
_GATHER_ENTRY_NS = ((17_770, 3.4), (480_189, 13.2))
_DENSE_PAIR_NS = 0.14
_DENSE_MIN_COUNT = 4096

# most bytes of one block's outer products, ``[_DENSE_BLOCK_ROWS, R^2]``
# float32 (`_dense_normal_equations`): no dense form from rank 363
_DENSE_OUTER_BYTES = 2 << 30

# share of one device's memory that one side's dense blocks may take
_DENSE_MEMORY_SHARE = 4


def _gather_entry_ns(n_opposite: int) -> float:
    """ns a padded entry of a gathered chunk costs against a table of
    ``n_opposite`` rows: `_GATHER_ENTRY_NS` at the two tables measured,
    interpolated by the logarithm of the rows between them.  Outside
    them the nearer reading is held: a guess, measured at no such
    table (a sharded table, the largest, never asks)."""
    (n0, ns0), (n1, ns1) = _GATHER_ENTRY_NS
    at = math.log(max(n_opposite, 1) / n0) / math.log(n1 / n0)
    return ns0 + min(max(at, 0.0), 1.0) * (ns1 - ns0)


def dense_min_count(n_opposite: int, rank: int,
                    float_weights: bool) -> Optional[int]:
    """Fewest ratings with which a row is staged DENSE against an
    opposite table of ``n_opposite`` rows, or None where no row is.

    A gathered row costs its pad width K (the next power of two of its
    ratings) times `_gather_entry_ns`, whatever the rank; a dense one
    ``n_opposite`` times `_DENSE_PAIR_NS` x (rank / 64)^2, twice that
    where its weights are float32 (``float_weights``: the implicit form
    over ratings that are not whole numbers, `dense_slots`).  So a row
    is dense from the least K that pays, and never once that K would
    be the whole table or a block's outer products pass
    ``_DENSE_OUTER_BYTES``; and from ``_DENSE_MIN_COUNT`` ratings."""
    share = (_DENSE_PAIR_NS * (rank / 64) ** 2 * (2 if float_weights else 1)
             / _gather_entry_ns(n_opposite))
    if share >= 1 or _DENSE_BLOCK_ROWS * 4 * rank ** 2 > _DENSE_OUTER_BYTES:
        return None
    least_k = 1 << (math.ceil(share * n_opposite) - 1).bit_length()
    return max(_DENSE_MIN_COUNT, least_k // 2 + 1)


def dense_slots(widest_pair: int, whole: bool, least: float,
                most: float) -> tuple:
    """``(count dtype, rating dtype)`` of a dense block's slots: the
    narrowest that hold every slot's sum exactly, from the ratings
    (``whole`` numbers, ``least`` to ``most``) and the most times one
    pair is held (``widest_pair``).  The count is int8 while a pair is
    held at most 127 times; the rating sum uint8 where the ratings are
    whole numbers from 0 and ``widest_pair`` x ``most`` stays under 256
    (the netflix table's stars: pairs held up to 45 times, sums up to
    167), float32 else, fractional ratings among them.  No slot is
    checked after it is built: these bounds are what let it hold."""
    count = np.dtype(np.int8) if widest_pair <= 127 else np.dtype(np.int32)
    narrow = whole and least >= 0 and widest_pair * most <= 255
    return count, np.dtype(np.uint8 if narrow else np.float32)


def dense_blocks(n_opposite: int) -> int:
    """Blocks of ``_DENSE_BLOCK_ROWS`` opposite rows in a dense chunk."""
    return -(-n_opposite // _DENSE_BLOCK_ROWS)


def dense_budget_rows(n_opposite: int, slot_bytes: int) -> int:
    """Most rows one side may stage dense: their ``[J, n_opposite]``
    slots of ``slot_bytes`` (`dense_slots`) stay under a quarter of ONE
    device's memory (16 GB against 480,189 opposite rows: 4,096 rows at
    two bytes, 1,024 at five), because one device builds a chunk's
    block whole before a mesh splits its rows."""
    row_bytes = dense_blocks(n_opposite) * _DENSE_BLOCK_ROWS * slot_bytes
    return _rows_in_memory_share(row_bytes, _DENSE_MEMORY_SHARE)


@dataclass(frozen=True)
class ALSConfig:
    rank: int = 10
    num_iterations: int = 20
    lam: float = 0.01
    implicit: bool = False
    alpha: float = 1.0
    seed: int = 3
    # λ·n_row·I (MLlib <=1.3 / ALS-WR) vs plain λ·I
    weighted_lambda: bool = True
    # truncate pathological rows beyond this many ratings (0 = no cap)
    max_ratings_per_row: int = 0
    min_bucket_k: int = 8
    # MXU precision for the Gram einsums: "highest" (f32), "high" (bf16x3),
    # "default" (bf16).  RMSE parity wants "highest"; ranking-only workloads
    # can trade down.
    matmul_precision: str = "highest"
    # batched SPD solver.  "auto" (the default) resolves from what the
    # code can observe (`_solve_path`): the ops/solve.py Cholesky kernel
    # for float32 systems of R <= 128 on a TPU backend, lax.linalg
    # everywhere else.  "xla" (lax.linalg) and "pallas" (the kernel,
    # through the interpreter on the CPU backend) force a path for
    # tests and A/B.  A kernel the backend's compiler rejects fails the
    # first half-iteration with the compiler's message; nothing is
    # substituted for it
    solver: str = "auto"
    # rank-sweep strategy: "full" solves the complete R×R normal
    # equations per row (today's behavior, the default); "subspace"
    # (iALS++, arXiv 2110.14044) sweeps the rank dimension in blocks of
    # ``subspace_size``, replacing each per-row O(R³) SPD solve with
    # R/B solves of B×B subsystems and the full [K,R]→R² Gram
    # contraction with rank-B updates against a cached residual —
    # per sweep: Gram work drops R/B-fold, solve work (R/B)²-fold.
    # ``subspace_size >= rank`` routes through the EXACT full-solve
    # code path (bitwise-identical results).
    solver_mode: str = "full"
    # block width B of the subspace sweep (ALX-friendly: smaller B×B
    # systems pack MORE rows per VMEM tile in the Pallas GJ kernel)
    subspace_size: int = 16
    # how the GATHERED buckets fetch the opposite rows (a row staged
    # dense, `dense_min_count`, reads the table in order and gathers
    # nothing): "row" (plain jnp.take) or "grouped" — gather
    # TILE-ALIGNED groups of 8 consecutive rows as one [G*R]-lane slab,
    # then take_along_axis the wanted row.  A rank-64 row is a fraction
    # of one (8,128) memory tile, so the plain row gather can move up to
    # 16x more bytes than it delivers; grouped reads move whole tiles
    # usefully.  Exact (same rows, same math) — the A/B is pure gather
    # bandwidth, measured on-chip by bench.py --gather-mode.
    gather_mode: str = "row"
    # -- pio-scout serve-time retrieval defaults ------------------------
    # Training never reads these; they ride the config object so one
    # ALSConfig describes a full train+serve deployment (bench.py and
    # programmatic servers configure one place; the templates map the
    # engine.json keys retrieval/candidateFactor/nprobe onto them).
    # "exact" = brute-force scan; "int8" = flat quantized candidate
    # stage + exact f32 rerank; "ivf" = candidates restricted to the
    # nprobe nearest coarse clusters (predictionio_tpu/retrieval/).
    retrieval: str = "exact"
    candidate_factor: int = 10
    nprobe: int = 8

    def __post_init__(self) -> None:
        # checked here, not at use sites: the use sites test exact
        # equality with an else-fallthrough, so a typo'd value would
        # silently run the default path (and these strings now arrive
        # straight from user engine.json files via the templates)
        if self.gather_mode not in ("row", "grouped"):
            raise ValueError(
                f"gather_mode must be 'row' or 'grouped', "
                f"got {self.gather_mode!r}"
            )
        if self.solver not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"solver must be 'auto', 'xla' or 'pallas', "
                f"got {self.solver!r}"
            )
        if self.solver_mode not in ("full", "subspace"):
            raise ValueError(
                f"solver_mode must be 'full' or 'subspace', "
                f"got {self.solver_mode!r}"
            )
        if self.solver_mode == "subspace" and self.subspace_size < 1:
            raise ValueError(
                f"subspace_size must be >= 1, got {self.subspace_size}"
            )
        if self.factor_placement not in ("replicated", "sharded"):
            raise ValueError(
                f"factor_placement must be 'replicated' or 'sharded', "
                f"got {self.factor_placement!r}"
            )
        if self.loss_every is not None and self.loss_every < 0:
            raise ValueError(
                f"loss_every must be >= 0, got {self.loss_every}"
            )
        if self.retrieval not in ("exact", "int8", "ivf"):
            raise ValueError(
                f"retrieval must be 'exact', 'int8' or 'ivf', "
                f"got {self.retrieval!r}"
            )
        if self.candidate_factor < 1:
            raise ValueError(
                f"candidate_factor must be >= 1, "
                f"got {self.candidate_factor}"
            )
        if self.nprobe < 1:
            raise ValueError(f"nprobe must be >= 1, got {self.nprobe}")
        if self.coded_shards:
            if self.factor_placement != "sharded":
                raise ValueError(
                    "coded_shards=True requires "
                    "factor_placement='sharded' (parity is a property "
                    "of the sharded table layout)"
                )
            if self.solver_mode == "subspace":
                # the subspace sweep transiently all-gathers the
                # UPDATING table too; serving that gather from parity as
                # well is a second code word this PR does not maintain —
                # refuse rather than silently run uncoded
                raise ValueError(
                    "coded_shards=True does not compose with "
                    "solver_mode='subspace' (the warm-start gather of "
                    "the updating table is not parity-protected)"
                )
    # factor-table placement on the mesh: "replicated" keeps both tables
    # on every device (fastest when they fit one chip's HBM); "sharded"
    # block-shards both tables over the ``data`` axis (ALX-style, arXiv
    # 2112.02194) so trainable model size scales with mesh HBM — the
    # opposite table is all-gathered transiently per half-iteration and
    # updates are written shard-locally
    factor_placement: str = "replicated"
    # coded-ALS straggler tolerance (arXiv 2105.03631; parallel/coded.py):
    # maintain a rotating parity block alongside the d factor shards so
    # a half-iteration whose shard is late/dead completes from the other
    # d-1 plus parity instead of stalling the ring.  Sharded-only.
    coded_shards: bool = False
    # per-half shard wait budget when coded (seconds): a shard whose
    # injected/observed lag stays within the budget is waited for; past
    # it the shard is served from parity.  0 = no budget — any
    # fault-flagged straggler degrades immediately (the deterministic
    # default the chaos suite pins)
    shard_hop_budget_s: float = 0.0
    # pio-tower sweep-loss cadence: compute training RMSE every N
    # sweeps over a seeded subsample of at most
    # ALSTrainer.LOSS_SAMPLE_MAX triples (one cached _sq_err_sum
    # dispatch — cost bounded at any scale; exact RMSE when the
    # dataset fits the cap).  0 disables; None = auto (every sweep).
    # The convergence watchdog's divergence check needs the loss; its
    # NaN check does not
    loss_every: Optional[int] = None


@dataclass
class ALSFactors:
    """The trained model: factor matrices as host arrays."""

    user_factors: np.ndarray  # [n_users, rank] float32
    item_factors: np.ndarray  # [n_items, rank] float32


# --------------------------------------------------------------------------
# Host-side preprocessing: COO -> bucket layout (indices only; the padded
# [B, K] blocks are expanded on device, once, at staging)
# --------------------------------------------------------------------------


@dataclass
class Bucket:
    k: int             # static pad width (power of two); DENSE_K: dense
    rows: np.ndarray   # [Bp] row ids; padding = n_rows (OOB -> dropped)
    starts: np.ndarray  # [Bp] offset of each row's slice in the sorted COO
    counts: np.ndarray  # [Bp] true rating count (<= k); 0 for padding


@dataclass
class BucketLayout:
    n_rows: int
    col_sorted: np.ndarray  # [nnz] opposite-side ids, grouped by row
    val_sorted: np.ndarray  # [nnz] ratings, grouped by row
    buckets: list[Bucket] = field(default_factory=list)


def build_bucket_layout(
    row_ix: np.ndarray,
    col_ix: np.ndarray,
    val: np.ndarray,
    n_rows: int,
    min_k: int = 8,
    max_per_row: int = 0,
    batch_multiple: int = 1,
    max_entries: Optional[int] = None,
    starts_dtype: type = np.int32,
    max_rows: Optional[int] = None,
    dense_min: Optional[int] = None,
    dense_rows: int = 0,
    owners: int = 1,
) -> BucketLayout:
    """Group rows by padded rating-count so the device solves static shapes.

    Rows with zero ratings are excluded (their factors stay at init, like
    MLlib which simply never solves them).  Oversized buckets are split so
    ``B*K <= max_entries`` and ``B <= max_rows`` (:func:`gram_chunk_rows`:
    the bytes of the chunk's Gram); batch dims are padded to
    ``batch_multiple`` (the mesh size) for even sharding.

    ``starts_dtype``: the replicated-COO path keeps int32 (those offsets
    are gathered on device) and rejects COOs past the int32 range; the
    sharded-COO path passes int64 — its device offsets are SHARD-LOCAL
    (``_plan_shard_layout``), so the global layout may exceed 2^31
    ratings as long as every per-device shard stays under it.
    """
    if starts_dtype == np.int32 and len(val) >= np.iinfo(np.int32).max:
        # Bucket.starts (and the on-device gather positions) are int32;
        # beyond 2^31 ratings the offsets would wrap. A single-replica COO
        # that large belongs on the sharded-COO path instead.
        raise ValueError(
            f"{len(val):,} ratings exceed the int32 offset range of a "
            "replicated bucket layout; use factor_placement='sharded' "
            "(sharded COO) or shard the COO across hosts first"
        )
    # O(n) native counting sort when the C++ runtime is available
    # (predictionio_tpu/native), NumPy argsort otherwise
    from ..native import sort_coo_by_row

    c_sorted, v_sorted, counts, starts = sort_coo_by_row(
        row_ix, col_ix, val, n_rows
    )
    layout = BucketLayout(
        n_rows=n_rows, col_sorted=c_sorted, val_sorted=v_sorted
    )
    layout.buckets = _assemble_buckets(
        counts, starts, n_rows, min_k, max_per_row, batch_multiple,
        max_entries, starts_dtype=starts_dtype, max_rows=max_rows,
        dense_min=dense_min, dense_rows=dense_rows, owners=owners,
    )
    return layout


def _deal_order(owner: np.ndarray) -> np.ndarray:
    """An order of a bucket's rows, from the shard that owns each
    (``owner``, non-decreasing as the ids ascend), in which every run of
    consecutive rows holds each owner's rows in proportion to its share
    of the bucket, to a row: owner o's j-th row of c stands at
    ``(j + 1/2) / c`` of the way.  Rows of one owner keep their order,
    and a bucket with one owner keeps its own."""
    held = np.bincount(owner)
    within = np.arange(len(owner)) - np.repeat(np.cumsum(held) - held, held)
    place = (within + 0.5) / np.repeat(held, held)
    return np.argsort(place, kind="stable")


def _assemble_buckets(
    counts: np.ndarray,
    starts: np.ndarray,
    n_rows: int,
    min_k: int = 8,
    max_per_row: int = 0,
    batch_multiple: int = 1,
    max_entries: Optional[int] = None,
    starts_dtype: type = np.int32,
    max_rows: Optional[int] = None,
    dense_min: Optional[int] = None,
    dense_rows: int = 0,
    owners: int = 1,
) -> list[Bucket]:
    """Bucket plan from per-row (counts, starts) alone.

    Shared by the host path (counts/starts from the counting sort) and the
    device-staging path (counts from ``np.bincount`` on the raw COO, starts
    from its cumsum — the big sorted arrays never touch the host there).

    Rows with ``dense_min`` ratings or more (:func:`dense_min_count`;
    None: no row) leave the K buckets for DENSE chunks (``k == DENSE_K``,
    after the others), the widest ``dense_rows`` of them
    (:func:`dense_budget_rows`) where more qualify.

    ``owners`` (sharded placement: the shards of the table of ``n_rows``
    rows, which they divide) above 1 DEALS a pad width's rows over its
    chunks (:func:`_deal_order`), so that every chunk holds each shard's
    rows in the shard's share of the bucket and no shard writes back a
    whole chunk while the others wait (`_owner_lists`).  At 1 the rows
    ascend, as the replicated halves' staged shapes and programs have
    them.
    """
    if max_entries is None:
        max_entries = MAX_ENTRIES_PER_BUCKET
    if max_per_row and max_per_row > 0:
        eff_counts = np.minimum(counts, max_per_row)
    else:
        eff_counts = counts

    # bucket key: next power of two of the (possibly capped) count —
    # computed for all rows at once (ceil(log2), floored at min_k)
    safe = np.maximum(eff_counts, 1)
    k_of_row = np.maximum(
        min_k, 1 << np.ceil(np.log2(safe)).astype(np.int64)
    )
    if dense_min is not None:
        wide = np.flatnonzero(eff_counts >= dense_min)
        wide = wide[np.argsort(-eff_counts[wide], kind="stable")[:dense_rows]]
        k_of_row[wide] = DENSE_K
    active = np.nonzero(counts)[0]
    k_active = k_of_row[active]

    buckets: list[Bucket] = []
    keys = np.unique(k_active)
    for k in (*keys[keys != DENSE_K], *keys[keys == DENSE_K]):
        k = int(k)
        rows_k = active[k_active == k].astype(np.int32)
        if owners > 1:
            rows_k = rows_k[_deal_order(rows_k // (n_rows // owners))]
        b_cap = dense_rows if k == DENSE_K else max_entries // k
        if max_rows is not None:
            b_cap = min(b_cap, max_rows)
        b_cap = max(batch_multiple, b_cap // batch_multiple * batch_multiple)
        for s in range(0, len(rows_k), b_cap):
            rows = rows_k[s : s + b_cap]
            B = len(rows)
            Bp = pad_to_multiple(max(B, batch_multiple), batch_multiple)
            # padding ids are distinct OOB values (n_rows, n_rows+1, ...):
            # the scatter drops them, and uniqueness stays honest for
            # unique_indices=True
            rows_p = n_rows + np.arange(Bp, dtype=np.int32)
            starts_p = np.zeros(Bp, dtype=starts_dtype)
            counts_p = np.zeros(Bp, dtype=np.int32)
            rows_p[:B] = rows
            starts_p[:B] = starts[rows]
            counts_p[:B] = eff_counts[rows]
            buckets.append(
                Bucket(k=k, rows=rows_p, starts=starts_p, counts=counts_p)
            )
    return buckets


def _gram_entries(buckets: list[Bucket]) -> dict:
    """A side's real ratings by the path their Gram takes."""
    entries = {"gathered": 0, "dense": 0}
    for b in buckets:
        entries["dense" if b.k == DENSE_K else "gathered"] += int(
            b.counts.sum())
    return entries


def _plan_shard_layout(
    buckets: list[Bucket], n_dev: int, build_perm: bool = True
) -> tuple[Optional[np.ndarray], list[np.ndarray], int]:
    """Shard-ordered COO plan: co-partition rating slices with the bucket
    rows each device solves.

    ``build_sharded_half`` splits every bucket's batch dim into ``n_dev``
    contiguous chunks (shard_map ``P('data')`` semantics).  This plan
    reorders the row-grouped COO so device ``d`` holds exactly the rating
    slices of the rows in ITS chunks, concatenated bucket by bucket — the
    TPU answer to MLlib's co-partitioned rating/factor blocks
    (`org.apache.spark.ml.recommendation.ALS` block layout; SURVEY
    §2.7(2)) and to ALX's sharded rating matrix (arXiv 2112.02194).

    Returns ``(perm, local_starts, L)``:

    * ``perm`` — ``[n_dev, L]`` int64 gather indices into the row-sorted
      COO; position ``(d, j)`` names the global rating that lands at
      shard-local offset ``j`` on device ``d`` (padding positions gather
      index 0; never read — every device access is masked by counts).
    * ``local_starts`` — per bucket, an int32 ``[Bp]`` array aligned with
      ``bucket.rows`` whose entries are offsets into the OWNING DEVICE's
      shard (replacing the global int32 starts whose range capped nnz).
    * ``L`` — the padded per-shard length (max over devices).

    The per-device nnz imbalance is bounded by one bucket row's worth of
    ratings per bucket (chunks differ by at most the count spread inside
    a bucket, and buckets group rows of similar padded size).

    ``build_perm=False`` skips materializing ``perm`` (total-nnz-sized)
    and returns ``None`` in its place — planning-only validation, e.g.
    checking the per-shard int32 ceiling at >2^31 global nnz without
    allocating the index.
    """
    offsets = np.zeros(n_dev, dtype=np.int64)
    local_starts: list[np.ndarray] = []
    starts_per_dev: list[list[np.ndarray]] = [[] for _ in range(n_dev)]
    counts_per_dev: list[list[np.ndarray]] = [[] for _ in range(n_dev)]
    for b in buckets:
        Bp = len(b.rows)
        assert Bp % n_dev == 0, "bucket batch dim not padded to mesh size"
        chunk = Bp // n_dev
        ls = np.zeros(Bp, dtype=np.int64)
        for d in range(n_dev):
            sl = slice(d * chunk, (d + 1) * chunk)
            cnts = b.counts[sl].astype(np.int64)
            ls[sl] = offsets[d] + np.concatenate(
                ([0], np.cumsum(cnts)[:-1])
            )
            offsets[d] += int(cnts.sum())
            starts_per_dev[d].append(np.asarray(b.starts[sl], np.int64))
            counts_per_dev[d].append(cnts)
        local_starts.append(ls)
    L = int(offsets.max()) if n_dev else 0
    if L >= np.iinfo(np.int32).max:
        raise ValueError(
            f"per-shard nnz {L:,} exceeds the int32 offset range; use "
            "more devices or shard across hosts"
        )
    if not build_perm:
        return None, [ls.astype(np.int32) for ls in local_starts], max(L, 1)
    perm = np.zeros((n_dev, max(L, 1)), dtype=np.int64)
    for d in range(n_dev):
        if not starts_per_dev[d]:
            continue
        starts_d = np.concatenate(starts_per_dev[d])
        counts_d = np.concatenate(counts_per_dev[d])
        total = int(counts_d.sum())
        if total:
            # vectorized multi-slice gather: for each row j, positions
            # starts_d[j] .. starts_d[j]+counts_d[j]-1 in order
            base = np.repeat(
                starts_d
                - np.concatenate(([0], np.cumsum(counts_d)[:-1])),
                counts_d,
            )
            perm[d, :total] = np.arange(total, dtype=np.int64) + base
    return perm, [ls.astype(np.int32) for ls in local_starts], max(L, 1)


def _owner_lists(rows: np.ndarray, shard_n: int, n_dev: int) -> tuple:
    """Which solved rows of a chunk each shard writes back, for a group
    of chunks ``rows [n, B]`` (global ids; batch padding, at or past the
    table's ``n_dev * shard_n`` rows, is nobody's): ``(own_pos, own_row)``,
    both int32 ``[n, n_dev, cap]``.  ``own_pos[c, s]`` are the places in
    chunk c's all-gathered ``[B]`` order of the rows shard s owns, and
    ``own_row[c, s]`` those rows' ids inside the shard.  ``cap`` is the
    most rows any shard owns of any one chunk, rounded up to the mesh:
    about B / n_dev for rows dealt over the chunks (:func:`_deal_order`),
    B for a group whose rows live in one shard.  Past a shard's count the
    places point at row 0 and the ids are distinct and out of range
    (``shard_n + slot``), so the scatter drops them and
    ``unique_indices=True`` stays honest, as for the replicated path's
    batch padding."""
    b = rows.shape[1]
    owner = np.minimum(rows // shard_n, n_dev).astype(np.int16)
    by_owner = np.argsort(owner, axis=1, kind="stable")
    held = np.stack([(owner == s).sum(axis=1) for s in range(n_dev)], axis=1)
    first = np.cumsum(held, axis=1) - held
    cap = pad_to_multiple(max(int(held.max()), 1), n_dev)
    slot = np.arange(cap)
    mine = slot < held[:, :, None]
    pos = np.take_along_axis(
        by_owner[:, None, :],
        np.minimum(first[:, :, None] + slot, b - 1), axis=2)
    local = (np.take_along_axis(rows[:, None, :], pos, axis=2)
             - (np.arange(n_dev) * shard_n)[:, None])
    return (np.where(mine, pos, 0).astype(np.int32),
            np.where(mine, local, shard_n + slot).astype(np.int32))


@xray.instrument("als.expand_sides")
@jax.jit
def _device_expand_sides(col_by_row, val_by_row, row_counts, val_scale):
    """Both sides' row-grouped ``(c_sorted, v_sorted)`` from a COO the
    HOST already counting-sorted by row (staging="device").

    Input is only ``(col_by_row, val_by_row, row_counts)`` in the
    narrowest lossless dtypes — the row-id column never crosses the
    host↔device link at all: the row side's grouping is the transfer
    order itself, and the row ids are reconstructed on device as
    ``repeat(arange(n_rows), row_counts)`` (exact because the host sort
    is ascending-stable).  The opposite side is one argsort over the col
    ids + gathers; the value decode to f32 happens after its gather so
    that big move stays narrow.  Ordering within a row is arbitrary,
    which the bucket layout permits.
    """
    nnz = col_by_row.shape[0]
    c_row = col_by_row.astype(jnp.int32)
    v_row = val_by_row.astype(jnp.float32) * val_scale
    rows = jnp.repeat(
        jnp.arange(row_counts.shape[0], dtype=jnp.int32), row_counts,
        total_repeat_length=nnz,
    )
    order = jnp.argsort(c_row)
    c_opp = jnp.take(rows, order)
    # value decode happens after its gather so that big move stays uint8
    v_opp = jnp.take(val_by_row, order).astype(jnp.float32) * val_scale
    return c_row, v_row, c_opp, v_opp


# a row's slice is 2.4 us, an element of a gather 21 ns: slices from here up
_SLICE_MIN_K = 128


def _valid_slots(counts, k: int):
    """``[B, K]`` mask of the slots that hold a rating."""
    return jnp.arange(k, dtype=jnp.int32)[None, :] < counts[:, None]


def _expand_bucket(c_sorted, v_sorted, starts, counts, k: int):
    """One bucket's padded ``[B, K]`` opposite-side ids and ratings from
    the row-grouped columns: row b's slots are ``c_sorted[starts[b] :
    starts[b] + counts[b]]``, the padding slots 0 / 0.0.  Staging's own
    (`_expand_side`, `_expand_side_sharded`, `_dense_block`): no sweep
    reads the columns.

    From ``_SLICE_MIN_K`` entries a row, read as B slices of length K,
    not B*K addresses: the TPU's per-element gather from a
    one-dimensional array costs 21 ns an element (the Netflix-size user
    side: 7.0 s against 1.1 s; PERF.md, PR 30).  Below it, as K single
    elements a row: a slice costs its 2.4 us whatever its width, and
    20 M rows of K = 8 were 27 s of a 40 s sweep (four chips, PR 34);
    the block's bits are the same either way.  The columns' tail is
    padded by K, so that no slice is clamped at the end and shifted.
    """
    valid = _valid_slots(counts, k)

    def slices(padded):
        return jax.vmap(
            lambda start: jax.lax.dynamic_slice(padded, (start,), (k,))
        )(starts)

    def elements(padded):
        return padded[starts[:, None]
                      + jnp.arange(k, dtype=starts.dtype)[None, :]]

    # k is a pad width, never a traced value
    read = slices if k >= _SLICE_MIN_K else elements  # piolint: disable=PIO104

    def block(column):
        rows = read(jnp.pad(column, (0, k)))
        return jnp.where(valid, rows, 0)

    return block(c_sorted), block(v_sorted)


@xray.instrument("als.expand_side")
@functools.partial(jax.jit, static_argnames=("ks",))
def _expand_side(c_sorted, v_sorted, starts_counts, *, ks):
    """Every bucket of one side expanded to its padded block, once, at
    staging: the half-iterations read the blocks and hold no gather
    from the ``[nnz]`` columns."""
    with jax.named_scope("als.positions"):
        return tuple(
            _expand_bucket(c_sorted, v_sorted, starts, counts, k)
            for (starts, counts), k in zip(starts_counts, ks)
        )


@xray.instrument("als.expand_side_sharded")
@functools.partial(jax.jit, static_argnames=("mesh", "ks"))
def _expand_side_sharded(c_sorted, v_sorted, starts_counts, *, mesh, ks):
    """`_expand_side` under sharded placement: every chunk group of one
    side (``[n, B]`` shard-local starts and counts, `_chunk_groups`)
    expanded to its ``[n, B, K]`` ids and ratings, once, at staging,
    each device reading its own ``[n, B/d]`` rows out of its own shard
    of the columns (``P('data')``; `_plan_shard_layout`).  The blocks
    stay where they are made, ``P(None, 'data', None)``; the sharded
    halves read them and hold no gather from the shard's COO.

    A chunk at a time: the TPU pads a ``[rows, 8]`` temporary to 128
    lanes, and a group's 5 M rows at once are 2.6 GB of positions."""
    from ..parallel.collectives import shard_map

    def body(c_shard, v_shard, *flat):
        with jax.named_scope("als.positions"):
            blocks = []
            for g, k in enumerate(ks):
                blocks += jax.lax.map(
                    lambda chunk, k=k: _expand_bucket(
                        c_shard, v_shard, *chunk, k),
                    (flat[2 * g], flat[2 * g + 1]),
                )
            return tuple(blocks)

    column, chunks = P(DATA_AXIS), P(None, DATA_AXIS)
    blocks = P(None, DATA_AXIS, None)
    flat = shard_map(
        body, mesh=mesh,
        in_specs=(column, column) + (chunks,) * (2 * len(ks)),
        out_specs=(blocks,) * (2 * len(ks)),
    )(c_sorted, v_sorted, *(a for pair in starts_counts for a in pair))
    return tuple(zip(flat[0::2], flat[1::2]))


def _expansion_report(padded, expand_s: float) -> dict:
    """What a staged side says of its padded blocks (``(idx, val)`` a
    bucket or chunk group), under either placement; the ``als.expand``
    phase is observed here, once a side."""
    TRAIN_PHASE_SECONDS.labels(phase="als.expand").observe(expand_s)
    return {
        "padded_entries": sum(idx.size for idx, _ in padded),
        "padded_bytes": sum(a.nbytes for blk in padded for a in blk),
        "expand_s": round(expand_s, 6),
    }


# entries of one slice of a dense row's ratings (`_dense_pieces`)
_DENSE_PIECE = 4096


def _dense_pieces(bucket: Bucket) -> tuple:
    """A dense chunk's ratings as slices of ``_DENSE_PIECE`` entries of
    the row-grouped columns: ``(row, start, length)`` of each slice, host
    arrays, ``row`` the row's place in the chunk.  The rows' widths
    differ sixteen-fold, so one pad width for all would be mostly
    padding; whole slices are what `_expand_bucket` reads cheaply."""
    counts = bucket.counts.astype(np.int64)
    per_row = -(-counts // _DENSE_PIECE)
    row = np.repeat(np.arange(len(counts)), per_row)
    within = (np.arange(len(row)) - np.repeat(np.cumsum(per_row) - per_row,
                                              per_row)) * _DENSE_PIECE
    return (
        row.astype(np.int32),
        (bucket.starts[row] + within).astype(bucket.starts.dtype),
        np.minimum(counts[row] - within, _DENSE_PIECE).astype(np.int32),
    )


# `_slot_stats` counts one pair's repeats up to this many (2^8): more
# would change no slot of `dense_slots`
_WIDEST_PAIR_SEEN = 256


@jax.jit
def _slot_stats(col, val, starts):
    """What `dense_slots` decides from, of a COO grouped by row with the
    opposite ids ascending inside a row (``starts``: each row's first
    place): the most times one pair is held (``_WIDEST_PAIR_SEEN`` where
    it is that or more), whether every rating is a whole number, the
    least rating and the greatest.

    A pair held L times is a run of L - 1 places that each hold the pair
    of the place before (``again``).  No prefix scan finds the longest:
    compiled for a v5e, one over the netflix table's 100 M ratings takes
    20 to 32 s, this form 3.  Runs of 2^j are found by doubling, the longest by
    binary lifting: the widest run that still extends it, widest
    first."""
    n = col.shape[0]
    first = jnp.zeros(n, bool).at[starts].set(True, mode="drop")
    again = (col == jnp.roll(col, 1)) & ~first
    steps = _WIDEST_PAIR_SEEN.bit_length() - 1

    def back(a, s):
        """``a[p - s]`` at p, False before the first place"""
        pad = _WIDEST_PAIR_SEEN
        return jax.lax.dynamic_slice(jnp.pad(a, (pad, 0)), (pad - s,), (n,))

    # levels[j][p]: the 2^j places up to p all hold again
    levels = [again]
    for j in range(steps - 1):
        levels.append(levels[j] & back(levels[j], 1 << j))
    run, length = jnp.ones(n, bool), jnp.int32(0)
    for j in reversed(range(steps)):
        longer = run & back(levels[j], length)
        found = longer.any()
        run = jnp.where(found, longer, run)
        length += jnp.where(found, 1 << j, 0)
    return (length + 1, jnp.all(val == jnp.round(val)), jnp.min(val),
            jnp.max(val))


def _slot_sums(at, x, shape, dtype):
    """``x`` summed into ``zeros(shape, dtype)`` at ``at`` (indices past
    the first axis's end dropped).  One-byte slots are summed four to a
    uint32 word, byte k of word w holding slot ``k * shape[-1] / 4 + w``,
    and the bytes then laid out in slot order: on a v5e a scatter of
    values into one-byte slots compiled for 75 to 108 s at the netflix
    item side's shapes, this form builds cold in 17 s (an int8 count
    and a float32 sum in 20).  A sum that left its byte would spill
    into the next slot; `dense_slots` sizes the slots so that none
    does."""
    if np.dtype(dtype).itemsize != 1:
        return jnp.zeros(shape, dtype).at[at].add(x.astype(dtype),
                                                  mode="drop")
    *lead, slot = at
    quarter = shape[-1] // 4
    shift = (8 * (slot // quarter)).astype(jnp.uint32)
    words = jnp.zeros((*shape[:-1], quarter), jnp.uint32).at[
        (*lead, slot % quarter)].add(x.astype(jnp.uint32) << shift,
                                     mode="drop")
    return jnp.concatenate([(words >> 8 * k) & 0xFF for k in range(4)],
                           axis=-1).astype(dtype)


@xray.instrument("als.dense_block")
@functools.partial(jax.jit,
                   static_argnames=("rows", "blocks", "count_dtype",
                                    "rating_dtype"))
def _dense_block(c_sorted, v_sorted, piece_row, piece_start, piece_len, *,
                 rows: int, blocks: int, count_dtype, rating_dtype):
    """One dense chunk's resident ``[blocks, rows, _DENSE_BLOCK_ROWS]``
    arrays, built once at staging: how many ratings row j holds of
    opposite row u (0 or 1 in a table without repeated pairs) and their
    sum, at ``[u // block, j, u % block]``."""
    idx, val = _expand_bucket(c_sorted, v_sorted, piece_start, piece_len,
                              _DENSE_PIECE)
    # a padding slot's block lies past the last: the scatter drops it
    block = jnp.where(_valid_slots(piece_len, _DENSE_PIECE),
                      idx // _DENSE_BLOCK_ROWS, blocks)
    at = (block, piece_row[:, None], idx % _DENSE_BLOCK_ROWS)
    shape = (blocks, rows, _DENSE_BLOCK_ROWS)
    return (_slot_sums(at, jnp.ones_like(idx), shape, count_dtype),
            _slot_sums(at, val, shape, rating_dtype))


def _dense_chunk(columns, bucket: Bucket, blocks: int, put,
                 slots: tuple) -> tuple:
    """``(count, rating)`` of one dense chunk (`_dense_block`) in the
    ``slots`` `dense_slots` chose."""
    count_dtype, rating_dtype = slots
    return _dense_block(
        *columns, *(put(a) for a in _dense_pieces(bucket)),
        rows=len(bucket.rows), blocks=blocks, count_dtype=count_dtype,
        rating_dtype=rating_dtype,
    )


def _dense_normal_equations(opp: jax.Array, count: jax.Array,
                            rating: jax.Array, alpha: jax.Array,
                            implicit: bool, prec) -> tuple:
    """``(A [J, R, R], b [J, R])`` of a dense chunk's J rows, without
    ``reg`` and the implicit ``YtY``: ``A = W @ Z`` and ``b = Bw @ opp``
    over ALL opposite rows, read in order, where ``Z[u, r*R + s] =
    opp[u, r] * opp[u, s]``, ``W`` is ``count`` (explicit) or ``alpha``
    x ``rating`` (implicit) and ``Bw`` is ``rating`` (explicit) or
    ``count`` + ``alpha`` x ``rating`` (implicit): the sums the gathered
    einsums take over a row's own entries, a repeated pair counted as
    often as it is held.  ``opp`` is the table itself: ``gather_mode``
    concerns the gathered buckets.

    Summed block by block in float32, the partial Grams added: ONE
    contraction over a popular row's 232,944 outer products would lose
    digits to its own running sum (`_table_gram`)."""
    f32 = jnp.float32
    blocks, j, block_rows = count.shape
    n, r = opp.shape
    opp_blocks = jnp.pad(
        opp.astype(f32), ((0, blocks * block_rows - n), (0, 0))
    ).reshape(blocks, block_rows, r)

    def dot(w, x):
        return jnp.dot(w, x, precision=prec, preferred_element_type=f32)

    def add_block(sums, block):
        c, v, o = block
        c, v = c.astype(f32), v.astype(f32)
        z = (o[:, :, None] * o[:, None, :]).reshape(block_rows, r * r)
        # alpha scales the sums below, so the weights here are the
        # resident blocks as they are (whole numbers from integer slots
        # take the MXU three bf16 passes at `highest`, not six); the
        # explicit form has no use for the third sum, a 64th of the
        # first one's work
        terms = (dot(v if implicit else c, z), dot(v, o), dot(c, o))
        return tuple(s + t for s, t in zip(sums, terms)), None

    (A, b, held), _ = jax.lax.scan(
        add_block,
        (jnp.zeros((j, r * r), f32),) + (jnp.zeros((j, r), f32),) * 2,
        (count, rating, opp_blocks),
    )
    if implicit:
        A, b = alpha.astype(f32) * A, held + alpha.astype(f32) * b
    return A.reshape(j, r, r), b


# --------------------------------------------------------------------------
# Device-side: one jitted half-iteration per direction
# --------------------------------------------------------------------------


# a bucket's batch dim, sharded over the mesh's data axis
_BATCH = P(DATA_AXIS)


def _per_device(fn, mesh: Optional[Mesh], in_specs, out_specs):
    """``fn`` run by every device of ``mesh`` on its own slice of the
    data-sharded batch; ``mesh=None`` (one device, or already inside a
    ``shard_map`` body) is ``fn`` itself.

    XLA partitions the replicated-placement half-iteration from its
    input shardings, but it cannot partition a Pallas kernel ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in
    a shard_map" — the 2x2 v5e host, PR 21), so the kernels are handed
    their shards explicitly.
    """
    if mesh is None:
        return fn
    from ..parallel.collectives import shard_map

    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs)


def _block_sweeps(solver_mode: str, subspace_size: int, r: int) -> bool:
    """Whether a half sweeps rank blocks (iALS++): ``subspace_size >= r``
    is the full solve VERBATIM, per the ALSConfig contract."""
    return solver_mode == "subspace" and 0 < subspace_size < r


# the widest system the ops/solve.py kernel is sized for: a 128-lane
# tile of [128, 128] float32 systems is 8 MiB of its VMEM budget
_KERNEL_MAX_R = 128


def _solve_path(solver: str, r: int, dtype=jnp.float32) -> str:
    """Which implementation solves a batch of ``r`` x ``r`` systems of
    ``dtype`` under ``ALSConfig.solver``: ``"kernel"`` (`ops/solve.py`)
    or ``"lax"`` (``lax.linalg``).

    ``"pallas"`` and ``"xla"`` force one; ``"auto"`` takes the kernel
    where it is the faster (a TPU backend, float32 systems no wider than
    a tile holds) and ``lax`` elsewhere: on the CPU backend the kernel
    would run through the Pallas interpreter.  The vmapped sweep
    (`sweep_train_als`) resolves ``"auto"`` to ``"xla"`` itself: a
    Pallas grid does not batch under ``vmap``.
    """
    if solver == "pallas":
        return "kernel"
    if (solver == "auto" and jax.default_backend() == "tpu"
            and dtype == jnp.float32 and r <= _KERNEL_MAX_R):
        return "kernel"
    return "lax"


def _spd_solve(A: jax.Array, b: jax.Array, solver: str,
               mesh: Optional[Mesh] = None) -> jax.Array:
    """Batched SPD solve ``A[i] x[i] = b[i]`` via the configured backend.

    One routing point for BOTH the full R×R systems and the subspace
    mode's B×B subsystems (:func:`_solve_path`): the Cholesky kernel
    (`ops/solve.py` — smaller systems pack more of the batch per VMEM
    tile) or the XLA Cholesky + two triangular solves.  ``mesh``: see
    :func:`_per_device`.
    """
    if _solve_path(solver, A.shape[-1], A.dtype) == "kernel":
        from ..ops.solve import cholesky_solve_batched

        return _per_device(
            cholesky_solve_batched, mesh, in_specs=(_BATCH, _BATCH),
            out_specs=_BATCH,
        )(A.astype(jnp.float32), b.astype(jnp.float32))
    L = jax.lax.linalg.cholesky(A)
    y = jax.lax.linalg.triangular_solve(
        L, b[..., None], left_side=True, lower=True
    )
    return jax.lax.linalg.triangular_solve(
        L, y, left_side=True, lower=True, transpose_a=True
    )[..., 0]


# rows of one partial sum of a table's Gram (`_table_gram`), and the
# bytes of partial Grams it holds at once
_GRAM_BLOCK_ROWS = 4096
_GRAM_STACK_BYTES = 256 << 20


def _table_gram(table: jax.Array, prec) -> jax.Array:
    """``Y^T Y`` of a tall ``[M, R]`` table in float32, summed in blocks
    of ``_GRAM_BLOCK_ROWS`` rows whose partial Grams are then added
    together.  ONE contraction over millions of rows adds each row's
    squares to a running sum thousands of times their size, and what the
    sum's last bit drops is digits of the result (the four-chip cell,
    PR 34: 2.4e-5 of the implicit half's solution, as much as computing
    at ``high``); a block's sum stays within 2^12 of its terms."""
    f32 = jnp.float32
    n, r = table.shape
    blocks = n // _GRAM_BLOCK_ROWS

    def gram(rows):
        return jnp.einsum("mr,ms->rs", rows, rows, precision=prec,
                          preferred_element_type=f32)

    def summed(head):
        return jax.lax.map(gram, head).sum(axis=0)

    # partial Grams held at once: 4,096 at rank 128, 16 at rank 2,048
    # (571,355 rows' 139 would be 2.3 GB); the groups' sums are added in
    # turn, a few tens of terms of one size
    held = max(1, _GRAM_STACK_BYTES // (4 * r * r))
    total = jnp.zeros((r, r), f32)
    if blocks:
        head = table[: blocks * _GRAM_BLOCK_ROWS].reshape(
            blocks, _GRAM_BLOCK_ROWS, r)
        if blocks <= held:
            total = summed(head)
        else:
            groups = blocks // held
            total = jax.lax.scan(
                lambda acc, group: (acc + summed(group), None), total,
                head[: groups * held].reshape(
                    groups, held, _GRAM_BLOCK_ROWS, r),
            )[0]
            if blocks % held:
                total = total + summed(head[groups * held:])
    if n % _GRAM_BLOCK_ROWS:
        total = total + gram(table[blocks * _GRAM_BLOCK_ROWS:])
    return total


# widest pad width, as a share of the rank, whose rows are solved in
# the K x K form (`_lowrank_form`): measured on one v5e chip (PERF.md,
# PR 37), one bucket of each K alone, a chunk with its gather, the full
# form against this one: rank 128, 8,192 rows, K = 32 11.7 against 7.5
# ms and K = 64 8.1 against 8.2; rank 64, 32,768 rows, K = 16 15.6
# against 12.6 and K = 32 23.8 against 26.1
_LOWRANK_RANK_SHARE = 4


def _lowrank_form(k: int, r: int, implicit: bool, solver_mode: str,
                  subspace_size: int) -> bool:
    """Whether a bucket of static pad width ``k`` at rank ``r`` is solved
    in its K x K form against the shared base (`_lowrank_solve`) and not
    as ``[B, R, R]`` normal equations.

    Implicit buckets alone have a base every row shares (``YtY``); the
    block sweep consumes the gathered rows by block, a dense bucket
    (``DENSE_K``) gathers nothing; and the form wins only while a row's
    ``k`` rotated rows and its K x K system cost less than an R x R Gram
    and factorisation: ``k`` no more than a quarter of the rank."""
    return (
        implicit and k != DENSE_K
        and not _block_sweeps(solver_mode, subspace_size, r)
        and _LOWRANK_RANK_SHARE * k <= r
    )


class _GramBase(NamedTuple):
    """``YtY`` in a basis that makes ``(YtY + reg I)^-1`` a scale by row:
    ``q^T YtY q = diag(lam) + rest`` with ``q`` orthonormal to float32's
    last bits and ``rest`` the little that float32's ``eigh`` leaves off
    the diagonal."""
    q: jax.Array      # [R, R]
    lam: jax.Array    # [R]
    rest: jax.Array   # [R, R]


def _matmul_t_compensated(a: jax.Array, b: jax.Array) -> tuple:
    """``a^T b`` of two small float32 matrices ``[n, p]``, ``[n, q]`` as
    a pair (sum, what the sum's roundings dropped): the n products of
    every entry are added one at a time, each addition's error kept
    (Knuth's two-sum).  A plain float32 contraction over 128 terms is
    good to 3e-7 of its largest partial sum, which `_gram_base` cannot
    afford: its products are every row's system."""
    def step(carry, ab):
        s, c = carry
        p = ab[0][:, None] * ab[1][None, :]
        t = s + p
        bp = t - s
        return (t, c + ((s - (t - bp)) + (p - bp))), None

    zero = jnp.zeros((a.shape[1], b.shape[1]), jnp.float32)
    return jax.lax.scan(step, (zero, zero), (a, b))[0]


def _gram_base(gram: jax.Array) -> _GramBase:
    """Once a half, from the ``YtY`` the half already has: float32's
    eigenvectors, made orthonormal to 1e-7 by a Newton-Schulz step on a
    compensated ``q^T q - I`` (``eigh`` leaves 2e-6 on the CPU and 6e-6
    on a v5e, and 1e-5 of ``YtY`` off the diagonal there), and
    ``q^T YtY q`` by compensated products, so that ``lam`` and ``rest``
    describe the ``YtY`` in hand to 5e-8 of it and not to the 3e-7 of a
    plain float32 product.  Four products of 128 steps over [R, R]
    arrays: 1.6 ms a half on a v5e (PERF.md, PR 37)."""
    hi = jax.lax.Precision.HIGHEST
    _, q = jnp.linalg.eigh(gram)
    qq, low = _matmul_t_compensated(q, q)
    eye = jnp.eye(gram.shape[-1], dtype=gram.dtype)
    q = q - 0.5 * jnp.matmul(q, (qq - eye) + low, precision=hi)
    gq, gq_low = _matmul_t_compensated(gram, q)      # YtY is symmetric
    t, low = _matmul_t_compensated(q, gq)
    low = low + jnp.matmul(q.T, gq_low, precision=hi)
    # YtY has no negative eigenvalue; a rounded one may read as such
    lam = jnp.maximum(jnp.diagonal(t), 0.0)
    rest = (t - jnp.diag(lam)) + low
    return _GramBase(q, lam, 0.5 * (rest + rest.T))


def _lowrank_solve(Vm, val, maskf, reg, alpha, base: _GramBase, prec,
                   solver: str, mesh, *, probe: bool = False):
    """An implicit bucket's rows ``[B, R]`` from its gathered, masked
    opposite rows ``Vm [B, K, R]`` where K is far under R, without the
    ``[B, R, R]`` normal equations.

    With ``G = YtY``, ``B_n = G + reg_n I`` and ``W = diag(sqrt(c - 1))
    Vm`` a row's system is ``(B_n + W^T W) x = b``, and (Woodbury)

        x = B_n^-1 b - B_n^-1 W^T (I_K + W B_n^-1 W^T)^-1 W B_n^-1 b

    the same system over the same entries and the whole ``YtY``, an
    identity.  In the basis of `_gram_base` ``B_n^-1`` is the scale
    ``1 / (lam + reg_n)`` by row, whatever the row's count, so the work
    is one rotation of the gathered rows, a K x K system (SPD whatever
    the row holds: padded slots are identity rows of it), and a rotation
    back.  What ``eigh`` left off the diagonal (``rest``) is taken in by
    one step of refinement against the exact rotated system, a
    ``[B, R] @ [R, R]`` product.  Takes ``c >= 1`` (``alpha * rating >=
    0``, Hu-Koren-Volinsky's confidence): a negative weight has no
    square root and reads NaN, which the sweep's watchdog reports.

    ``probe``: the sum of the K x K systems and right-hand sides in the
    rows' place (the phase probe's ``stop_after="gram"``)."""
    f32 = jnp.float32
    k = Vm.shape[1]

    def over_slots(coef, rows):
        """``sum_k coef[b, k] rows[b, k, :]``."""
        return jnp.einsum("bk,bkr->br", coef, rows, precision=prec)

    def along_rank(rows, vec):
        """``sum_r rows[b, k, r] vec[b, r]``."""
        return jnp.einsum("bkr,br->bk", rows, vec, precision=prec)

    with jax.named_scope("als.lowrank_rotate"):
        Vt = jnp.einsum("bkr,rs->bks", Vm, base.q,
                        precision=prec, preferred_element_type=f32)
    with jax.named_scope("als.lowrank_system"):
        cw = alpha.astype(f32) * val * maskf             # (c - 1), f32
        d = 1.0 / (base.lam[None, :] + reg[:, None])     # [B, R]
        sw = jnp.sqrt(cw)
        Wt = sw[..., None] * Vt                          # [B, K, R]
        S = jnp.eye(k, dtype=f32) + jnp.einsum(
            "bkr,bjr->bkj", Wt * d[:, None, :], Wt, precision=prec)
        # b = Vm^T cb, and the answer is B_n^-1 Vm^T coef with
        # coef = cb - sqrt(c-1) S^-1 W B_n^-1 b: where a prediction is
        # near 1 (alpha 40, a trained table) that difference cancels
        # digits.  On the slots with a weight, sqrt(c-1) S^-1 (cb /
        # sqrt(c-1)) is the same number with nothing to cancel; a real
        # entry whose rating is 0 has no weight and keeps its cb
        has_weight = cw > 0
        cb = (1.0 + cw) * maskf
        beta = jnp.where(has_weight, cb / jnp.where(has_weight, sw, 1.0), 0.0)
        cb0 = jnp.where(has_weight, 0.0, cb)
        rhs = beta - along_rank(Wt, d * over_slots(cb0, Vt))
    if probe:
        return S.sum() + rhs.sum()
    with jax.named_scope("als.lowrank_solve"):
        coef = sw * _spd_solve(S, rhs, solver, mesh) + cb0
        wt = over_slots(coef, Vt)
        # what eigh left off the diagonal: one step of refinement
        # against the exact rotated system (diag(lam) + rest + reg +
        # Wt^T Wt) xt = bt, whose residual at xt = d * wt is -xt @ rest
        rt = jnp.einsum("br,rs->bs", d * wt, base.rest, precision=prec)
        z = _spd_solve(S, along_rank(Wt, d * rt), solver, mesh)
        wt = wt - rt + over_slots(z, Wt)
        w = over_slots(coef + sw * z, Vm)
    with jax.named_scope("als.lowrank_rotate"):
        # B_n^-1 = mid I + q diag(d - mid) q^T with mid the least of d:
        # what all directions share needs no rotation, so a base near a
        # multiple of the identity (a seed's tables) leaves the rotation
        # back little to round, and no term is a difference
        mid = d.min(axis=1)[:, None]
        return mid * w + jnp.einsum(
            "bs,rs->br", (d - mid) * wt - mid * rt, base.q, precision=prec)


def _half_iteration_impl(
    upd: jax.Array,        # [N, R] factor table being solved (donated)
    opp: jax.Array,        # [M, R] opposite-side factor table
    bucket_args: tuple,    # tuple of (rows, idx, val, counts) per bucket
    lam: jax.Array,        # traced scalar: sweeping λ must not recompile
    alpha: jax.Array,      # traced scalar
    *,
    ks: tuple,             # static: pad width per bucket
    implicit: bool,
    weighted_lambda: bool,
    precision: str,
    solver: str,
    gather_mode: str = "row",
    solver_mode: str = "full",
    subspace_size: int = 0,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    def write(acc, rows, x):
        acc = upd if acc is None else acc
        # batch-padding rows carry row id >= N -> dropped by the scatter
        return acc.at[rows].set(
            x.astype(acc.dtype), mode="drop", unique_indices=True
        )

    out = _solve_staged(
        write, upd, opp, bucket_args, lam, alpha,
        ks=ks, implicit=implicit, weighted_lambda=weighted_lambda,
        precision=precision, solver=solver, gather_mode=gather_mode,
        solver_mode=solver_mode, subspace_size=subspace_size, mesh=mesh,
    )
    return upd if out is None else out


# jitted entry point; the impl stays reachable for vmapped λ sweeps
# (sweep_train_als), where the batching transform must see the raw fn.
# xray.instrument feeds the recompile detector: a λ sweep reuses the
# executable (traced scalar -> same signature) while a bucket-layout or
# rank change shows up as a signature delta on /debug/xray.
_half_iteration = xray.instrument("als.half_iteration")(
    functools.partial(
        jax.jit,
        static_argnames=(
            "ks", "implicit", "weighted_lambda", "precision", "solver",
            "gather_mode", "solver_mode", "subspace_size", "mesh",
        ),
        donate_argnums=(0,),
    )(_half_iteration_impl)
)


@xray.instrument("als.phase_probe")
@functools.partial(
    jax.jit,
    static_argnames=(
        "ks", "implicit", "weighted_lambda", "precision", "solver",
        "gather_mode", "solver_mode", "subspace_size", "stop_after",
    ),
)
def _half_phase_probe(upd, opp, bucket_args, lam, alpha, *, ks, implicit,
                      weighted_lambda, precision, solver,
                      gather_mode="row", solver_mode="full",
                      subspace_size=0, stop_after="gather"):
    """Truncated half-iteration for pio-obs phase tracing: the real
    half's kernel prefix (gather only / gather+Gram), jitted WITHOUT
    donation — the real, donating half still consumes ``upd`` right
    after the probes run."""
    return _solve_staged(
        None, upd, opp, bucket_args, lam, alpha,
        ks=ks, implicit=implicit, weighted_lambda=weighted_lambda,
        precision=precision, solver=solver, gather_mode=gather_mode,
        solver_mode=solver_mode, subspace_size=subspace_size,
        stop_after=stop_after,
    )


def _solve_staged(upd_write, upd, opp, bucket_args, lam, alpha, *,
                  solver_mode: str, subspace_size: int, **how):
    """A replicated half over the buckets as `ALSTrainer._stage_side`
    staged them: the block sweep's runs of chunks as loops
    (`_block_sweep_half`), the full solve's buckets one unrolled step
    each (`_solve_buckets`)."""
    how.update(solver_mode=solver_mode, subspace_size=subspace_size)
    if _block_sweeps(solver_mode, subspace_size, upd.shape[-1]):
        return _block_sweep_half(upd_write, upd, opp, bucket_args, lam,
                                 alpha, **how)
    return _solve_buckets(upd_write, opp, bucket_args, lam, alpha,
                          upd_table=upd, **how)


def _als_phase_trace_enabled() -> bool:
    """``PIO_TPU_TRACE_ALS=1`` arms per-phase span recording.  Opt-in
    because honest phase timing needs a ``block_until_ready`` per probe
    and per half — the async dispatch pipelining ``run()`` normally
    rides is exactly what the waits suspend (same trade bench.py
    makes)."""
    import os

    return os.environ.get("PIO_TPU_TRACE_ALS") == "1"


def _solve_buckets(
    upd_write,             # callback(rows, x) -> new upd table/shard
    opp: jax.Array,        # [M, R] full opposite table (local or gathered)
    bucket_args: tuple,    # (rows, idx, val, counts) per bucket
    lam: jax.Array,
    alpha: jax.Array,
    *,
    ks: tuple,
    implicit: bool,
    weighted_lambda: bool,
    precision: str,
    solver: str,
    gather_mode: str = "row",
    solver_mode: str = "full",
    subspace_size: int = 0,
    upd_table: Optional[jax.Array] = None,
    gram: Optional[jax.Array] = None,
    base: Optional[_GramBase] = None,
    stop_after: Optional[str] = None,
    mesh: Optional[Mesh] = None,
    exchange=None,
):
    """Shared bucket-solve math for the replicated and sharded paths
    (and the pio-live fold-in: `live/foldin.py` routes its
    fixed-capacity single-bucket row solves through this same function
    with a write callback that returns the solved block, so online
    fold-in and offline training can never drift apart numerically).

    ``solver_mode="subspace"`` (iALS++, arXiv 2110.14044) replaces the
    per-row full R×R normal-equation solve with a sweep over rank
    blocks of width ``subspace_size``: per block S, a Newton step on
    the block coordinates — exact because the objective is quadratic —

        H_S δ = -(g_S),  x_S ← x_S + δ

    where ``H_S`` is the B×B principal Gram submatrix (+ reg) and
    ``g_S`` the block gradient evaluated against an incrementally
    maintained per-row residual (explicit) / prediction + YtY·x caches
    (implicit).  Per sweep the Gram contraction drops from O(K·R²) to
    O(K·B·R) and the solves from O(R³) to O(R·B²).  The sweep warm-
    starts from the CURRENT factor row, read from ``upd_table`` (the
    full — possibly all-gathered — table being updated; required for
    subspace mode).  ``subspace_size >= R`` takes the full-solve branch
    below verbatim, so the degenerate config is bitwise-identical to
    ``solver_mode="full"``.

    ``stop_after`` ("gather" | "gram") truncates the per-bucket pipeline
    and returns a scalar reduction instead of writing factors — used by
    ``bench.py --phase-probe`` to attribute per-iteration cost to
    gather vs MXU vs solver against the REAL kernel (no drift-prone
    copy of this math in the bench).

    ``gram`` (implicit mode only) lets the sharded path supply the YtY
    matrix computed shard-locally + psum'd instead of redundantly from the
    gathered full table.

    ``mesh`` is the multi-device mesh of the REPLICATED-placement caller
    (whose bucket batches arrive data-sharded): the Pallas kernels then
    run per device (:func:`_per_device`).  The sharded path calls this
    from inside its own ``shard_map`` body and leaves it None.

    A DENSE bucket (``k == DENSE_K``; staged under replicated placement
    with the full solve, by `ALSTrainer._dense_slots`)
    carries its ``[blocks, J, block_rows]`` counts and ratings in the
    place of ``idx`` and ``val``: its normal equations are a blocked
    matmul over ALL of ``opp`` (`_dense_normal_equations`), then the
    same ``reg``, solve and scatter as any bucket's.  Replicated
    placement over a mesh shards its J rows as it shards B.

    An implicit bucket whose pad width is small against the rank
    (`_lowrank_form`, from the static ``k`` and ``r``) builds no
    ``[B, R, R]``: its rows are solved in their K x K form against the
    shared ``YtY`` base (`_lowrank_solve`).  ``base`` is that base's
    decomposition (`_gram_base`), from a caller that comes here once a
    chunk and so computes it once a half itself.

    ``exchange`` (`parallel/collectives.ShardedRows`; the sharded path)
    says that ``opp`` is this device's ``[M/d, R]`` shard and the
    buckets' ids are global: the gather below then looks up all
    devices' ids in the shard and a reduce-scatter returns this
    device's rows, the same bits the gather from a whole table reads.
    """
    r = opp.shape[-1]
    sub = _block_sweeps(solver_mode, subspace_size, r)
    if sub and upd_table is None:
        raise ValueError(
            "solver_mode='subspace' requires the current factor table "
            "(upd_table) to warm-start the block sweep"
        )
    prec = jax.lax.Precision(
        {"highest": "highest", "high": "high", "default": "default"}[precision]
    )
    if implicit and gram is None:
        gram = _table_gram(opp, prec)
    lowrank = [
        stop_after != "gather" and _lowrank_form(
            k, r, implicit, solver_mode, subspace_size)
        for k in ks
    ]
    # static, as every k is
    if base is None and any(lowrank):  # piolint: disable=PIO104
        base = _gram_base(gram)
    f32 = jnp.float32
    opp_grp = None
    grp = _GATHER_GROUP_ROWS
    if gather_mode == "grouped":
        # tile-aligned slab gather (ALSConfig.gather_mode).  The slab
        # table is the 3D view [M/G, G, R] — the SAME row-major bytes,
        # but XLA tiles the trailing (G, R) dims, so one gathered [G, R]
        # slice is whole (8,128) tiles.  (The 2D [M/G, G*R] form would
        # lay the G rows along LANES: a slab row is then 1 sublane tall
        # and every gather still pays the full tile-height waste it was
        # meant to eliminate.)
        mg = -(-opp.shape[0] // grp) * grp
        opp_grp = jnp.pad(
            opp, ((0, mg - opp.shape[0]), (0, 0))
        ).reshape(mg // grp, grp, r)
    out = None

    def regularisation(counts):
        n_row = counts.astype(f32)                       # [B]
        lam_t = lam.astype(f32)
        if weighted_lambda:
            return lam_t * jnp.maximum(n_row, 1.0)       # ALS-WR: λ·n_row
        return jnp.broadcast_to(lam_t, n_row.shape)

    def solve_and_write(out, rows, A, b, reg):
        with jax.named_scope("als.gram"):
            A = A + reg[:, None, None] * jnp.eye(r, dtype=A.dtype)
        if stop_after == "gram":
            return (0.0 if out is None else out) + A.sum() + b.sum()
        with jax.named_scope("als.solve"):
            x = _spd_solve(A, b, solver, mesh)
        with jax.named_scope("als.scatter"):
            return upd_write(out, rows, x)

    # the als.* scopes name each bucket's steps in the HLO metadata, so a
    # profile finds the kernels by name whatever XLA fuses them into
    for (rows, idx, val, counts), k, low in zip(bucket_args, ks, lowrank):
        # k is a static pad width, never a traced value
        if k == DENSE_K:  # piolint: disable=PIO104
            if stop_after == "gather":
                continue        # a dense bucket gathers nothing
            with jax.named_scope("als.dense_gram"):
                A, b = _dense_normal_equations(opp, idx, val, alpha,
                                               implicit, prec)
                if implicit:
                    A = gram + A
            out = solve_and_write(out, rows, A, b, regularisation(counts))
            continue
        if low and out is not None:  # piolint: disable=PIO104
            # a replicated half unrolls its chunks, and nothing but the
            # table orders them: without a [B, R, R] to weigh on it the
            # TPU's scheduler starts hundreds of chunks' gathers and
            # rotations at once (41 GB of temporaries for 640 chunks of
            # 8,192 rows at rank 128, compiled for a v5e; PR 37).  This
            # chunk's ids wait for the last chunk's scatter
            out, idx = jax.lax.optimization_barrier((out, idx))
        with jax.named_scope("als.positions"):
            valid = _valid_slots(counts, k)
            maskf = valid.astype(f32)
        reg = regularisation(counts)
        with jax.named_scope("als.gather"):
            if exchange is not None:
                idx, valid_g = exchange.spread(idx, valid)
            else:
                valid_g = valid
            if opp_grp is not None:
                # slab gather + in-slab select: exact same rows as the
                # row gather, but every HBM read is a full memory tile.
                # The [*, K, G, R] slab is G times the row gather's
                # output, so it's produced in row-chunks bounded by
                # _GROUPED_SLAB_BYTES — the select shrinks each chunk
                # back to [*, K, R] before the next one materializes.
                bsz, k_ = idx.shape
                per_row = k_ * grp * r * 4
                bc = max(
                    1, min(bsz, _GROUPED_SLAB_BYTES // max(per_row, 1))
                )

                def _slab_rows(ix):
                    rows_n = ix.shape[0]
                    slab = jnp.take(opp_grp, ix // grp, axis=0)  # [n,K,G,R]
                    sel = jnp.broadcast_to(
                        (ix % grp)[..., None, None], (rows_n, k_, 1, r)
                    )
                    return jnp.take_along_axis(
                        slab, sel, axis=2
                    )[..., 0, :]

                if bc >= bsz:
                    Vm = _slab_rows(idx)
                else:
                    Vm = jnp.concatenate(
                        [
                            _slab_rows(idx[lo : lo + bc])
                            for lo in range(0, bsz, bc)
                        ],
                        axis=0,
                    )
                Vm = Vm * valid_g[..., None].astype(Vm.dtype)
            else:
                Vm = opp[idx] * valid_g[..., None].astype(
                    opp.dtype
                )                                            # [B, K, R]
            if exchange is not None:
                if low:  # piolint: disable=PIO104
                    # as [d*B*K, R]: the K x K form's consumers make the
                    # TPU's compiler lay a [B, K, R] operand out K-major,
                    # whose rows a reduce-scatter cannot scatter, and the
                    # exchange became an all-reduce of all four devices'
                    # rows (`als_exchange_s.x4` 2.50 -> 3.20; PR 37)
                    Vm = exchange.collect(
                        Vm.reshape(-1, r)).reshape(-1, k, r)
                else:
                    Vm = exchange.collect(Vm)
        if stop_after == "gather":
            out = (0.0 if out is None else out) + Vm.sum()
            continue
        # low and sub are static (the rule, the mode), never traced
        if low or sub:  # piolint: disable=PIO104
            if low:  # piolint: disable=PIO104
                res = _lowrank_solve(
                    Vm, val, maskf, reg, alpha, base, prec, solver, mesh,
                    probe=stop_after == "gram")
            else:
                # iALS++ block sweep: warm-start from the current factor
                # rows (batch-padding ids are OOB -> fill 0; their output
                # is dropped by the scatter anyway)
                x0 = upd_table.at[rows].get(
                    mode="fill", fill_value=0.0
                ).astype(f32)
                cw_b = (alpha.astype(f32) * val * maskf) if implicit \
                    else None
                res = _subspace_sweep(
                    Vm, val, maskf, x0, reg, cw_b, gram, prec, solver,
                    subspace_size, gram_probe=stop_after == "gram",
                    mesh=mesh,
                )
            if stop_after == "gram":
                out = (0.0 if out is None else out) + res
            else:
                with jax.named_scope("als.scatter"):
                    out = upd_write(out, rows, res)
            continue
        with jax.named_scope("als.gram"):
            if implicit:
                cw = alpha.astype(f32) * val * maskf     # (c - 1), f32
                A = gram + jnp.einsum(
                    "bk,bkr,bks->brs", cw, Vm, Vm,
                    precision=prec, preferred_element_type=f32,
                )
                b = jnp.einsum(
                    "bk,bkr->br", (1.0 + cw) * maskf,
                    Vm, precision=prec, preferred_element_type=f32,
                )
            else:
                A = jnp.einsum("bkr,bks->brs", Vm, Vm, precision=prec,
                               preferred_element_type=f32)
                b = jnp.einsum(
                    "bk,bkr->br", val * maskf, Vm,
                    precision=prec, preferred_element_type=f32,
                )
        out = solve_and_write(out, rows, A, b, reg)
    return out


def _sum_over_entries(spec: str, *operands, prec) -> jax.Array:
    """``jnp.einsum(spec, *operands)`` in float32 where the spec sums
    over a bucket's K axis (``k``, the operands' second axis, ``b``
    their first): summed in blocks of ``_GRAM_BLOCK_ROWS`` entries whose
    partial sums are then added, for the reason `_table_gram` is.  A
    row of the full solve this wide is staged dense; the block sweep
    gathers it, 131,072 entries to a contraction."""
    f32 = jnp.float32
    k = operands[0].shape[1]
    if k <= _GRAM_BLOCK_ROWS:
        return jnp.einsum(spec, *operands, precision=prec,
                          preferred_element_type=f32)
    # a pad width is a power of two
    parts = k // _GRAM_BLOCK_ROWS
    ins, out = spec.split("->")
    blocked = ",".join(t.replace("bk", "bck") for t in ins.split(","))
    return jnp.einsum(
        f"{blocked}->bc{out[1:]}",
        *(a.reshape(a.shape[0], parts, _GRAM_BLOCK_ROWS, *a.shape[2:])
          for a in operands),
        precision=prec, preferred_element_type=f32,
    ).sum(axis=1)


def _subspace_sweep(
    Vm: jax.Array,          # [B, K, R] gathered+masked opposite rows
    val: jax.Array,         # [B, K] masked ratings, f32
    maskf: jax.Array,       # [B, K] validity mask, f32
    x0: jax.Array,          # [B, R] current factor rows, f32
    reg: jax.Array,         # [B] per-row regularization (λ or λ·n_row)
    cw: Optional[jax.Array],  # [B, K] implicit (c-1) weights, or None
    gram: Optional[jax.Array],  # [R, R] YtY (implicit mode), f32
    prec,
    solver: str,
    block: int,
    *,
    gram_probe: bool = False,
    mesh: Optional[Mesh] = None,
):
    """One iALS++ rank-block sweep over a bucket's rows (arXiv
    2110.14044 Alg. 2, batched over rows).

    Each block update is an exact Newton step on the block coordinates
    of the quadratic per-row objective, against caches maintained
    incrementally with rank-B work:

    * explicit — residual ``e = Vm·x - val`` ([B, K]); block gradient
      ``g_S = VsᵀE + reg·x_S``, Hessian ``H_S = VsᵀVs + reg·I``.
    * implicit — prediction ``p = Vm·x`` and ``q = x·YtY`` ([B, R]);
      ``g_S = q_S + Vsᵀ((c-1)p - c) + reg·x_S``,
      ``H_S = YtY[S,S] + Vsᵀdiag(c-1)Vs + reg·I``.

    ``gram_probe=True`` computes every block's (H, g) without solving
    or updating the caches and returns their scalar sum — the
    ``stop_after="gram"`` hook that keeps the per-phase timing probe
    honest for this mode (the Gram-contraction cost of a sweep, minus
    the solve/update half).
    """
    f32 = jnp.float32
    r = Vm.shape[-1]
    pred = jnp.einsum(
        "bkr,br->bk", Vm, x0,
        precision=prec, preferred_element_type=f32,
    )
    # the cache a block's gradient reads and its update advances: the
    # residual (explicit) or the prediction (implicit, with q = x·YtY)
    implicit = cw is not None
    cache = pred if implicit else pred - val
    q = jnp.einsum("bs,sr->br", x0, gram, precision=prec) if implicit \
        else None

    def block_step(state, s, w: int):
        """The block of ``w`` rank coordinates from ``s`` on (a Python
        int, or the loop's traced offset)."""
        x, cache, q, acc = state
        with jax.named_scope("als.block_gram"):
            Vs = jax.lax.dynamic_slice_in_dim(Vm, s, w, axis=2)  # [B, K, w]
            xs = jax.lax.dynamic_slice_in_dim(x, s, w, axis=1)   # [B, w]
            if implicit:
                gram_rows = jax.lax.dynamic_slice_in_dim(gram, s, w, axis=0)
                H = jax.lax.dynamic_slice_in_dim(
                    gram_rows, s, w, axis=1
                ) + _sum_over_entries(
                    "bk,bks,bkt->bst", cw, Vs, Vs,
                    prec=prec,
                )
                # (c-1)·p - c on rated items: cw is masked, so c·mask is
                # maskf + cw
                coef = cw * cache - maskf - cw
                g = jax.lax.dynamic_slice_in_dim(q, s, w, axis=1) \
                    + _sum_over_entries(
                        "bk,bks->bs", coef, Vs, prec=prec)
            else:
                H = _sum_over_entries("bks,bkt->bst", Vs, Vs, prec=prec)
                g = _sum_over_entries(
                    "bk,bks->bs", cache, Vs, prec=prec)
            H = H + reg[:, None, None] * jnp.eye(w, dtype=H.dtype)
            g = g + reg[:, None] * xs
        if gram_probe:
            return x, cache, q, acc + H.sum() + g.sum()
        with jax.named_scope("als.block_solve"):
            d = -_spd_solve(H, g, solver, mesh)              # [B, w]
        with jax.named_scope("als.block_update"):
            x = jax.lax.dynamic_update_slice_in_dim(x, xs + d, s, axis=1)
            cache = cache + jnp.einsum(
                "bks,bs->bk", Vs, d,
                precision=prec, preferred_element_type=f32)
            if implicit:
                q = q + jnp.einsum("bs,sr->br", d, gram_rows,
                                   precision=prec)
        return x, cache, q, acc

    # the whole blocks are ONE loop: one traced body, and one lowering of
    # the solve kernel, whatever the rank (16 blocks at rank 2,048 would
    # be 16 copies of the body in every bucket shape's program); a
    # narrower last block follows it
    state = (x0, cache, q, jnp.zeros((), f32))
    whole = r // block
    if whole > 1:
        state = jax.lax.fori_loop(
            0, whole, lambda j, st: block_step(st, j * block, block), state)
    else:
        state = block_step(state, 0, block)
    if r % block:
        state = block_step(state, whole * block, r % block)
    return state[3] if gram_probe else state[0]


def _block_sweep_half(upd_write, upd, opp, bucket_args, lam, alpha, *,
                      ks: tuple, implicit: bool, precision: str,
                      stop_after: Optional[str] = None, **how):
    """The block sweep's half under replicated placement: every staged
    bucket in turn, a run of chunks of one shape (arrays ``[n, B, ...]``,
    `ALSTrainer._stage_side`) as ONE loop, so that the hundreds of
    chunks a high rank makes (`gather_chunk_entries`) trace and lower
    one body a shape.

    A chunk's warm start is read from the table AS IT STANDS, the
    loop's carry, and its solved rows are written into the same: a row
    is solved once a half, so these are the bits the table held when
    the half began, and no second copy of the table being updated
    lives beside the first (4.7 GB at 571,355 x 2,048).  ``stop_after``:
    the phase probe's sums in the table's place; ``upd_write`` is then
    None."""
    gram = None
    if implicit:
        gram = _table_gram(opp, jax.lax.Precision(precision))
    probe = stop_after is not None
    carry = jnp.zeros((), jnp.float32) if probe else upd
    for bucket, k in zip(bucket_args, ks):
        def step(carry, chunk, k=k):
            table = upd if probe else carry

            def write(acc, rows, x):
                return upd_write(table if acc is None else acc, rows, x)

            out = _solve_buckets(
                None if probe else write, opp, (chunk,), lam, alpha,
                ks=(k,), implicit=implicit, precision=precision,
                upd_table=table, gram=gram, stop_after=stop_after, **how,
            )
            if probe:
                return carry if out is None else carry + out
            return table if out is None else out

        # a lone chunk is staged [B, ...], a run of n chunks [n, B, ...]
        carry = step(carry, bucket) if bucket[0].ndim == 1 \
            else _each_chunk(step, carry, bucket)
    return carry


def _chunk_groups(buckets: list) -> list:
    """Runs of consecutive bucket chunks of one shape (pad width and
    batch): ``[[0, 1, 2], [3], ...]``.  A run is staged as ONE stacked
    array and solved as one loop; `_assemble_buckets` emits a bucket's
    full chunks first and its remainder last, so a pad width makes two
    runs at most."""
    def shape(b):
        return b.k, len(b.rows)

    groups: list = []
    for j, b in enumerate(buckets):
        if groups and shape(buckets[groups[-1][0]]) == shape(b):
            groups[-1].append(j)
        else:
            groups.append([j])
    return groups


def _each_chunk(step, carry, chunks: tuple):
    """``carry = step(carry, chunk)`` over the leading axis of
    ``chunks`` (arrays ``[n, ...]``): a `lax.scan`, so n chunks of one
    shape trace and lower ``step`` once; a lone chunk is the call
    itself."""
    if chunks[0].shape[0] == 1:
        return step(carry, tuple(a[0] for a in chunks))
    return jax.lax.scan(
        lambda c, chunk: (step(c, chunk), None), carry, chunks
    )[0]


def build_sharded_half(
    mesh: Mesh,
    *,
    ks: tuple,
    implicit: bool,
    weighted_lambda: bool,
    precision: str,
    solver: str,
    gather_mode: str = "row",
    solver_mode: str = "full",
    subspace_size: int = 0,
    coded: bool = False,
):
    """ALX-style half-iteration over block-sharded factor tables.

    Layout (SURVEY §2.7(2); the TPU answer to MLlib's block-partitioned
    ALS, reference `examples/scala-parallel-similarproduct/multi/src/main/
    scala/ALSAlgorithm.scala`):

    * Both factor tables live **sharded** ``P('data', None)`` at rest, so
      model capacity scales with total mesh HBM instead of one chip's.
    * No device ever holds the opposite table, or more of it than its
      own shard: a chunk's opposite rows come through
      `parallel/collectives.ShardedRows` (the chunk's ids all-gathered,
      each device's look-up in its own shard, a reduce-scatter of the
      ``[d*B, K, R]`` partial answers), the same bits a gather from the
      whole table reads.  Each device then solves its shard of the
      chunk, all-gathers the small solved blocks ``[B, R]`` and writes
      only the rows its own factor shard owns, from a list laid out at
      staging (`_owner_lists`): the TPU's row scatter costs by the row
      it is handed (some 70 ns at rank 128), dropped or not, and a pad
      width's rows are dealt over its chunks (`_deal_order`) so that a
      chunk's rows are about a quarter each shard's and no shard
      scatters a whole chunk while the others wait for it.
    * The ratings are SHARDED with the rows that read them: staging
      lays each device's rating slices out in shard-local order
      (``_plan_shard_layout``; the int32-offset ceiling applies per
      shard) and expands them there, once, to the padded blocks
      (`_expand_side_sharded`), so a device holds the ids and ratings
      of the bucket rows it solves and no other — rating capacity
      scales with mesh HBM like MLlib's co-partitioned rating blocks.
      A half reads the blocks: it takes no argument of the shard's COO
      length and gathers nothing from a one-dimensional array.
    * ``ks[g]`` is the pad width of chunk group ``g``, whose arrays are
      the replicated path's own tuple laid out by chunk, ``(rows [n, B],
      idx [n, B, K], val [n, B, K], counts [n, B])``, the batch split
      over the mesh, and the owners' lists ``(own_pos, own_row)``, both
      ``[n, d, cap]``, a shard its own: n chunks of one shape
      (``_chunk_groups``), run as ONE loop, so a table of 600 chunks
      traces, lowers and compiles one chunk's program a shape.

    The subspace sweep keeps one whole-table gather, because it reads a
    whole table: it all-gathers the table being UPDATED for its warm
    start.

    ``coded=True`` (coded-ALS, arXiv 2105.03631; `parallel/coded.py`)
    builds the straggler-tolerant variant, which reconstructs a late
    shard's block inside the gathered table and so keeps the all-gather.
    Signature grows two inputs and one output::

        fn(upd, opp, opp_parity, ok_mask, lam, alpha, *buckets)
          -> (new_upd, new_upd_parity)

    ``opp_parity`` is the replicated ``[M/d, R]`` f32 block sum of the
    opposite table; ``ok_mask`` a replicated ``[d]`` 0/1 vector.  A
    masked (late/dead) opposite block is reconstructed in-program as
    ``parity - sum(alive blocks)`` — exact while parity is current —
    and the masked shard's OWN rows are frozen at their previous values
    (the dead worker wrote nothing this half).  The returned parity is
    the block sum of the UPDATED table, so the next half's opposite
    parity is already fresh.  With an all-ones mask the math reduces to
    the plain path (reconstruction multiplies by zero).

    Requires row counts padded to a multiple of the mesh size.  Bucket
    padding rows carry ids >= the padded row count and are in no
    shard's list; a list's own padding carries distinct ids past the
    shard's rows, which the scatter drops.
    """
    from ..parallel.collectives import (
        EXCHANGE_SCOPE, ShardedRows, shard_map,
    )

    axis = DATA_AXIS
    d = mesh.shape[axis]
    f32 = jnp.float32

    def solve_core(upd, opp, gram, lam, alpha, flat_buckets, exchange=None):
        # subspace mode warm-starts each row's block sweep from the
        # CURRENT factor value, but this device solves rows owned by
        # OTHER shards — gather the full updating table transiently
        # too (f32: it is the iterate, not a bandwidth-discountable
        # operand).  One extra [N, R] all-gather per half-iteration;
        # stays zero-cost when the mode is off or degenerate.
        upd_full = None
        if _block_sweeps(solver_mode, subspace_size, upd.shape[-1]):
            upd_full = jax.lax.all_gather(upd, axis, axis=0, tiled=True)
        # the K x K form's base, once a half and not once a chunk: every
        # device decomposes the same psum'd YtY
        base = None
        if any(_lowrank_form(k, upd.shape[-1], implicit, solver_mode,
                             subspace_size) for k in ks):
            base = _gram_base(gram)

        def chunk_step(k):
            def step(table, chunk):
                *bucket, own_pos, own_row = chunk

                def write(acc, rows, x):
                    acc = table if acc is None else acc
                    with jax.named_scope(EXCHANGE_SCOPE):
                        xg = jax.lax.all_gather(x, axis, axis=0, tiled=True)
                    # this shard's own rows of the chunk, by the staged
                    # list ([1, cap] here): the scatter costs by the row
                    # it is handed, dropped or not
                    return acc.at[own_row[0]].set(
                        xg[own_pos[0]].astype(acc.dtype), mode="drop",
                        unique_indices=True)

                out = _solve_buckets(
                    write, opp, (tuple(bucket),), lam, alpha,
                    ks=(k,), implicit=implicit,
                    weighted_lambda=weighted_lambda,
                    precision=precision, solver=solver,
                    gather_mode=gather_mode, solver_mode=solver_mode,
                    subspace_size=subspace_size, upd_table=upd_full,
                    gram=gram, base=base, exchange=exchange,
                )
                return table if out is None else out

            return step

        table = upd
        for g, k in enumerate(ks):
            table = _each_chunk(
                chunk_step(k), table, flat_buckets[6 * g : 6 * g + 6]
            )
        return table

    def _prec():
        return jax.lax.Precision(
            {"highest": "highest", "high": "high", "default": "default"}[
                precision
            ]
        )

    P_ = P
    sharded2 = P_(axis, None)
    rep = P_()
    # a group's (rows, idx, val, counts) and (own_pos, own_row), as staged
    bucket_specs = (
        P_(None, axis), P_(None, axis, None), P_(None, axis, None),
        P_(None, axis), P_(None, axis, None), P_(None, axis, None),
    ) * len(ks)

    if not coded:

        def body(upd, opp, lam, alpha, *flat_buckets):
            # upd/opp arrive as local shards [Np/d, R] / [Mp/d, R]
            gram = None
            if implicit:
                # YtY from the LOCAL shard + psum: identical [R, R]
                # result at 1/d the FLOPs of redoing the full einsum on
                # every device
                with jax.named_scope(EXCHANGE_SCOPE):
                    gram = jax.lax.psum(_table_gram(opp, _prec()), axis)
            return solve_core(
                upd, opp, gram, lam, alpha, flat_buckets,
                exchange=ShardedRows(axis, opp.shape[0]),
            )

        in_specs = (sharded2, sharded2, rep, rep) + bucket_specs
        mapped = shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=sharded2,
        )
        return xray.instrument("als.sharded_half")(
            jax.jit(mapped, donate_argnums=(0,))
        )

    def coded_body(upd, opp, opp_parity, ok, lam, alpha, *flat_buckets):
        me = jax.lax.axis_index(axis)
        # mask the late/dead shard's block out of the gather, then put
        # its reconstruction back: parity - sum(alive).  With all shards
        # alive the recon block multiplies by zero and the math is the
        # plain gather.
        okm = ok[me].astype(opp.dtype)
        alive = opp * okm
        gathered = jax.lax.all_gather(alive, axis, axis=0, tiled=True)
        recon = opp_parity - jax.lax.psum(alive, axis)
        blocks = gathered.reshape((d,) + opp.shape)
        okb = ok.reshape((d,) + (1,) * opp.ndim).astype(opp.dtype)
        opp_full = (blocks * okb + recon[None] * (1.0 - okb)).reshape(
            gathered.shape)
        gram = None
        if implicit:
            # per-shard gram + psum would read the dead shard's data;
            # fold the reconstructed block in explicitly instead
            alive_gram = jax.lax.psum(
                jnp.einsum("mr,ms->rs", alive, alive, precision=_prec()),
                axis,
            )
            gram = alive_gram + jnp.einsum(
                "mr,ms->rs", recon, recon, precision=_prec(),
            ) * (1.0 - jnp.min(ok))
        out = solve_core(upd, opp_full, gram, lam, alpha, flat_buckets)
        # a degraded shard wrote nothing this half: freeze its rows
        out = out * okm + upd * (1.0 - okm)
        # parity of the UPDATED table — the next half's opposite parity
        new_parity = jax.lax.psum(out.astype(f32), axis)
        return out, new_parity

    in_specs = (sharded2, sharded2, rep, rep, rep, rep) + bucket_specs
    mapped = shard_map(
        coded_body, mesh=mesh, in_specs=in_specs,
        out_specs=(sharded2, rep),
    )
    return xray.instrument("als.coded_half")(
        jax.jit(mapped, donate_argnums=(0,))
    )


class ALSTrainer:
    """Staged ALS state: build once, iterate cheaply.

    Separates the one-time host preprocessing + device staging from the
    iteration loop so that serving-time retrains, benchmarks, and
    warm-started sweeps don't re-pay staging.
    """

    def __init__(
        self,
        ratings: Ratings | tuple[np.ndarray, np.ndarray, np.ndarray],
        n_users: Optional[int] = None,
        n_items: Optional[int] = None,
        cfg: ALSConfig = ALSConfig(),
        mesh: Optional[Mesh] = None,
        staging: str = "auto",
    ):
        if isinstance(ratings, Ratings):
            u, i, v = ratings.user_ix, ratings.item_ix, ratings.rating
            n_users = ratings.n_users
            n_items = ratings.n_items
        else:
            u, i, v = ratings
            assert n_users is not None and n_items is not None
        self.cfg = cfg
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        self.n_users = n_users
        self.n_items = n_items

        n_dev = self.mesh.size if self.mesh is not None else 1
        # sharded factor tables need a real mesh and row counts divisible
        # by it; single-device "sharded" degenerates to replicated
        self.sharded = (
            cfg.factor_placement == "sharded" and self.mesh is not None
        )
        # single-device "sharded" degenerates to replicated, and coded
        # parity with it (there is no ring to straggle)
        self.coded = False
        self._pad_users = pad_to_multiple(n_users, n_dev)
        self._pad_items = pad_to_multiple(n_items, n_dev)
        nu = self._pad_users if self.sharded else n_users
        ni = self._pad_items if self.sharded else n_items
        caps = self._chunk_caps(n_dev)
        if staging not in ("auto", "host", "device"):
            raise ValueError(
                f"staging must be 'auto', 'host' or 'device', got {staging!r}"
            )
        if self.sharded:
            # sharded placement stages a SHARDED COO: each device holds
            # only the rating slices of the bucket rows it solves
            # (_plan_shard_layout), so nnz capacity scales with mesh HBM
            # and the int32-offset ceiling applies per shard, not
            # globally.  device_put with a P('data') sharding moves each
            # byte to exactly one device — there is no replicated copy
            # to avoid, so the staging knob is moot here.
            if staging != "auto":
                logger.warning(
                    "staging=%r is ignored under factor_placement="
                    "'sharded': the sharded-COO layout has its own "
                    "staging path (trainer.staging == 'sharded')",
                    staging,
                )
            self.staging = "sharded"
            self._user_side = self._stage_side_sharded(
                build_bucket_layout(
                    u, i, v, nu, cfg.min_bucket_k,
                    cfg.max_ratings_per_row, batch_multiple=n_dev,
                    starts_dtype=np.int64, **caps,
                ),
                n_dev,
            )
            self._item_side = self._stage_side_sharded(
                build_bucket_layout(
                    i, u, v, ni, cfg.min_bucket_k,
                    cfg.max_ratings_per_row, batch_multiple=n_dev,
                    starts_dtype=np.int64, **caps,
                ),
                n_dev,
            )
        elif staging == "auto":
            # device staging pays an extra device program (one argsort +
            # repeat/gathers); worth it once the sorted-COO transfer
            # dwarfs that (big datasets), not for the small problems
            # tests and templates mostly train
            staging = "device" if len(v) >= 2_000_000 else "host"
        if not self.sharded:
            self.staging = staging
            if staging == "device":
                sides = self._stage_device(u, i, v, nu, ni, n_dev)
                self._user_side, self._item_side = sides
            else:
                u, i = np.asarray(u), np.asarray(i)
                counts_u = np.bincount(u, minlength=nu)

                def by_pair():
                    order = np.lexsort((i, u))
                    return (i[order], np.asarray(v, np.float32)[order],
                            np.cumsum(counts_u) - counts_u)

                slots = self._dense_slots(
                    counts_u, np.bincount(i, minlength=ni), by_pair)
                self._user_side = self._stage(
                    build_bucket_layout(
                        u, i, v, nu, cfg.min_bucket_k,
                        cfg.max_ratings_per_row, batch_multiple=n_dev,
                        **caps, **self._dense_caps(ni, slots),
                    ),
                    ni, slots,
                )
                self._item_side = self._stage(
                    build_bucket_layout(
                        i, u, v, ni, cfg.min_bucket_k,
                        cfg.max_ratings_per_row, batch_multiple=n_dev,
                        **caps, **self._dense_caps(nu, slots),
                    ),
                    nu, slots,
                )
        if self.sharded:
            self._build_sharded_halves()
        self._init_loss(u, i, v)
        sides = {"user": self._user_side, "item": self._item_side}

        def per_side(key):
            # the dense keys are the replicated staging's alone
            return {name: side.get(key, 0) for name, side in sides.items()}

        self._plan_solves()
        self._plan_exchange()
        self._plan_gram_counters()
        staged = {
            "solver": cfg.solver,
            "solvePath": self.solve_path,
            "solveSystems": self.solve_systems,
            "solveForms": {
                name: {"lowrank": lowrank, "full": systems - lowrank}
                for name, systems in self.solve_systems.items()
                for lowrank in [sum(self.lowrank_systems[name].values())]
            },
            "lowrankWidths": {
                name: {str(k): rows for k, rows in sorted(by_width.items())}
                for name, by_width in self.lowrank_systems.items()
            },
            "solveSlabWork": self.slab_work,
            "staging": self.staging,
            "placement": "sharded" if self.sharded else "replicated",
            "shards": n_dev if self.sharded else 1,
            "oppTransientBytes": self.opp_transient_bytes,
            "gramChunkBytes": self.gram_chunk_bytes,
            "gatherChunkBytes": self.gather_chunk_bytes,
            "gatherBytes": self.gather_bytes,
            "chunksLooped": self.chunks_looped,
            "solverMode": cfg.solver_mode,
            "subspaceSize": cfg.subspace_size,
            "rankBlocks": -(-cfg.rank // self.system_width),
            "exchangeBytes": self.exchange_bytes,
            "writeRows": self.write_rows,
            "writeCaps": self.write_caps,
            "devices": n_dev,
            "devicesWithData": self.data_devices(),
            "paddedEntries": per_side("padded_entries"),
            "paddedBytes": per_side("padded_bytes"),
            "expandSeconds": per_side("expand_s"),
            "denseRows": per_side("dense_rows"),
            "denseEntries": {name: side["entries"]["dense"]
                             for name, side in sides.items()},
            "denseBytes": per_side("dense_bytes"),
            "denseChunks": per_side("dense_chunks"),
            "denseRatingDtype": {name: side.get("dense_rating_dtype")
                                 for name, side in sides.items()},
        }
        logger.info("ALS staged: %s", staged)
        tower.note_event("als_staged", **staged)

    def _chunk_caps(self, n_dev: int) -> dict:
        """The bounds every staging path hands `_assemble_buckets`: a
        chunk's rows by the bytes of its Gram (``[B, w, w]`` where a
        half sweeps rank blocks of width w, ``[B, R, R]`` else) and its
        entries by the bytes of its rows: the exchanged ones under
        sharded placement, the gathered ones else; and, under sharded
        placement, the shards that own the rows."""
        cfg = self.cfg
        caps = {
            "max_rows": gram_chunk_rows(self.system_width, n_dev),
            "max_entries": (
                exchange_chunk_entries(cfg.rank, n_dev) if self.sharded
                else gather_chunk_entries(cfg.rank, n_dev)
            ),
        }
        if self.sharded:
            # a pad width's rows dealt over its chunks by owning shard
            caps["owners"] = n_dev
        return caps

    @property
    def system_width(self) -> int:
        """Width of the systems a half solves: the rank block's where it
        sweeps blocks (`_block_sweeps`), the rank's else."""
        cfg = self.cfg
        return cfg.subspace_size if self.sweeps_blocks else cfg.rank

    @property
    def sweeps_blocks(self) -> bool:
        cfg = self.cfg
        return _block_sweeps(cfg.solver_mode, cfg.subspace_size, cfg.rank)

    def _dense_slots(self, counts_u, counts_i, grouped) -> Optional[tuple]:
        """The slots of the replicated staging's dense blocks
        (`dense_slots`, from `_slot_stats` of the COO that ``grouped()``
        returns), or None where no row can be staged dense: no row of
        either side (``counts_u``, ``counts_i``) holds the ratings
        `dense_min_count` asks at the least, or the gathered rows
        themselves are consumed: the subspace sweep.  (The sharded
        staging paths never ask: the opposite table is a shard there, and
        the dense form would be a partial Gram and a ``psum``, which is
        not built.)"""
        cfg = self.cfg
        if cfg.solver_mode == "subspace":
            return None
        if not any(
            least is not None and counts.max(initial=0) >= least
            for counts, n_opposite in ((counts_u, self.n_items),
                                       (counts_i, self.n_users))
            for least in [dense_min_count(n_opposite, cfg.rank, False)]
        ):
            return None
        widest, whole, least, most = _slot_stats(*grouped())
        return dense_slots(int(widest), bool(whole), float(least),
                           float(most))

    def _dense_caps(self, n_opposite: int, slots: Optional[tuple]) -> dict:
        """What the replicated staging paths hand `_assemble_buckets`
        besides, for a side whose opposite table has ``n_opposite``
        rows: from how many ratings a row is staged dense, and how many
        rows may be, in the ``slots`` of `_dense_slots`; nothing, so no
        dense row, where those are None."""
        if slots is None:
            return {}
        cfg = self.cfg
        count, rating = slots
        return {
            "dense_min": dense_min_count(
                n_opposite, cfg.rank, cfg.implicit and rating == np.float32),
            "dense_rows": dense_budget_rows(
                n_opposite, count.itemsize + rating.itemsize),
        }

    def _plan_solves(self) -> None:
        """What `_spd_solve` will be handed in every half, and by which
        path (``solve_path``, ``solve_systems`` a side): known on the
        host from the staged shapes alone.  A system for each row of
        each bucket, batch padding included, times the rank blocks of a
        subspace sweep.  Beside them the split by form: the rows whose
        system is solved K x K against the shared base
        (``lowrank_systems`` a side, by pad width; `_lowrank_form`),
        which ``solve_systems`` counts too; the others are solved at
        ``system_width``.  Where the kernel solves them, the share of
        its full-width slab products it does at each width
        (``slab_work`` a side).  And ``pio_als_solve_systems_total``'s
        children with what `run` adds to each once a sweep: the K x K
        rows under ``path="lowrank"``, the rest under ``solve_path``."""
        cfg = self.cfg
        width = self.system_width
        self.solve_path = _solve_path(cfg.solver, width)
        sides = (("user", self._user_side), ("item", self._item_side))
        self.solve_systems = {
            name: -(-cfg.rank // width) * sum(
                int(bucket[0].size) for bucket in side["buckets"])
            for name, side in sides
        }
        self.lowrank_systems = {name: {} for name, _ in sides}
        for name, side in sides:
            by_width = self.lowrank_systems[name]
            for bucket, k in zip(side["buckets"], side["ks"]):
                if _lowrank_form(k, cfg.rank, cfg.implicit, cfg.solver_mode,
                                 cfg.subspace_size):
                    by_width[k] = by_width.get(k, 0) + int(bucket[0].size)
        lowrank = sum(sum(by_width.values())
                      for by_width in self.lowrank_systems.values())
        # the share of the full-width slab products the kernel does at
        # each width it solves (`ops/solve.slab_work_share`)
        self.slab_work = {name: {} for name, _ in sides}
        if self.solve_path == "kernel":
            from ..ops.solve import slab_work_share

            for name, _ in sides:
                widths = set(self.lowrank_systems[name])
                if self.solve_systems[name] > sum(
                        self.lowrank_systems[name].values()):
                    widths.add(width)
                    if self.sweeps_blocks and cfg.rank % width:
                        widths.add(cfg.rank % width)
                self.slab_work[name] = {
                    str(w): slab_work_share(w) for w in sorted(widths)
                }
        self._solve_counters = [
            (ALS_SOLVE_SYSTEMS_TOTAL.labels(path=path), systems)
            for path, systems in (
                (self.solve_path, sum(self.solve_systems.values()) - lowrank),
                ("lowrank", lowrank),
            ) if systems
        ]

    def _plan_gram_counters(self) -> None:
        """``pio_als_gram_entries_total``'s children with what `run`
        adds to each once a sweep (the real ratings whose Gram a half
        builds by each path), looked up here and not in every sweep: a
        small table's sweep is a millisecond, and its record reconciles
        the phases with the whole to 2 %."""
        self._gram_entry_counters = [
            (ALS_GRAM_ENTRIES_TOTAL.labels(path=path, side=name), entries)
            for name, side in (("user", self._user_side),
                               ("item", self._item_side))
            for path, entries in side["entries"].items() if entries
        ]

    def _plan_exchange(self) -> None:
        """What a half moves between devices and holds in their place,
        from the staged shapes alone, a side (the side being solved):

        * ``exchange_bytes``: bytes ONE device receives in a half
          (sharded placement: each chunk's ids all-gathered, the
          reduce-scatter of its ``[d*B, K, R]`` partial rows, the solved
          ``[B, R]`` blocks all-gathered, the implicit YtY all-reduced;
          the whole table where the coded half all-gathers it).
        * ``write_rows``: rows ONE device hands its scatter in a half
          (sharded placement: every chunk's list, ``cap`` rows, padding
          included: a quarter of the side's padded rows on four devices
          where the rows are dealt over the chunks, all of them for a
          group that one shard owns), and ``write_caps``, each group's
          ``[cap, B]``.
        * ``opp_transient_bytes``: the most a device holds in the
          opposite table's place at once (the partial rows and the
          chunk's own; the whole table where the coded half all-gathers
          it).
        * ``gram_chunk_bytes``: the largest chunk's float32 Gram on one
          device, ``[B, w, w]`` for systems of width w (`system_width`).
        * ``gather_chunk_bytes``: the largest chunk's gathered rows
          ``[B, K, R]`` on one device; and
          ``gather_bytes``, all that a half gathers over the devices
          together, padding included (a dense chunk gathers nothing).
        * ``chunks_looped``: chunks that run inside a loop over chunks
          of their shape (under replicated placement the block sweep's
          alone: the full solve unrolls).
        """
        cfg = self.cfg
        r = cfg.rank
        d = self.mesh.size if self.mesh is not None else 1
        row_bytes = r * 4
        table_rows = {"user": self._pad_items, "item": self._pad_users}
        whole_opp = self.coded
        sub = self.sweeps_blocks
        self.exchange_bytes, self.opp_transient_bytes = {}, {}
        self.chunks_looped, self.gather_bytes = {}, {}
        self.write_rows, self.write_caps = {}, {}
        gram_rows = gather_entries = 0
        for name, side in (("user", self._user_side),
                           ("item", self._item_side)):
            received = transient = looped = gathered = 0
            for bucket, k in zip(side["buckets"], side["ks"]):
                rows = bucket[0]
                # a run of n chunks of one shape is staged [n, B]
                n = rows.shape[0] if rows.ndim == 2 else 1
                b = rows.shape[-1] // d
                gram_rows = max(gram_rows, b)
                gather_entries = max(gather_entries, b * k)
                gathered += rows.size * k * row_bytes
                looped += n if n > 1 else 0
                if not self.sharded:
                    continue
                received += n * (d - 1) * b * r * 4
                if not whole_opp:
                    received += n * (d - 1) * b * k * (4 + row_bytes)
                    transient = max(transient, (d + 1) * b * k * row_bytes)
            if self.sharded:
                shard = table_rows[name] // d * row_bytes
                if whole_opp:
                    received += (d - 1) * shard
                    transient = d * shard
                if sub:
                    upd_rows = {"user": self._pad_users,
                                "item": self._pad_items}[name]
                    received += (d - 1) * upd_rows // d * r * 4
                if cfg.implicit:
                    received += 2 * (d - 1) * r * r * 4 // d
            lists = [own_pos.shape for own_pos, _ in side.get("owners", ())]
            self.write_rows[name] = sum(n * cap for n, _, cap in lists)
            self.write_caps[name] = [
                [cap, bucket[0].shape[-1]]
                for (_, _, cap), bucket in zip(lists, side["buckets"])]
            self.exchange_bytes[name] = int(received)
            self.opp_transient_bytes[name] = int(transient)
            self.chunks_looped[name] = int(looped)
            self.gather_bytes[name] = int(gathered)
        self.gram_chunk_bytes = int(gram_rows * self.system_width ** 2 * 4)
        self.gather_chunk_bytes = int(gather_entries * row_bytes)

    def data_devices(self) -> int:
        """How many devices hold staged training data: the per-bucket
        rows, counts and padded blocks, under either placement.  What a
        multi-chip bring-up checks: code that put everything on device
        0 reports 1."""
        devices: set = set()
        for side in (self._user_side, self._item_side):
            for bucket in side["buckets"]:
                for a in bucket:
                    devices.update(a.devices())
        return len(devices)

    # per-sweep loss sample cap: the watchdog needs a *trajectory*, not
    # the exact training RMSE, so the loss pass runs over a fixed
    # seeded subsample of at most this many triples — its cost is
    # BOUNDED regardless of dataset scale (a full-COO pass was a
    # measured ~11% tax on the scale-0.02 CPU train; 64Ki samples put
    # the same trajectory at ~2%).  nnz <= the cap means the loss is
    # the exact training RMSE.
    LOSS_SAMPLE_MAX = 1 << 16

    def _init_loss(self, u, i, v) -> None:
        """Stage the (sub)sampled COO triples for the per-sweep loss
        pass (pio-tower).  ``cfg.loss_every`` None = auto: every sweep
        (the sample cap keeps it cheap at any scale)."""
        every = self.cfg.loss_every
        if every is None:
            every = 1
        self.loss_every = every
        if not every or len(v) == 0:
            self._loss_coo = None
            self.loss_sample_n = 0
        elif len(v) > self.LOSS_SAMPLE_MAX:
            pick = np.random.default_rng(self.cfg.seed).choice(
                len(v), size=self.LOSS_SAMPLE_MAX, replace=False,
            )
            pick.sort()
            self._loss_coo = (
                np.ascontiguousarray(np.asarray(u)[pick]),
                np.ascontiguousarray(np.asarray(i)[pick]),
                np.ascontiguousarray(
                    np.asarray(v)[pick].astype(np.float32)),
            )
            self.loss_sample_n = int(self.LOSS_SAMPLE_MAX)
        else:
            self._loss_coo = (
                np.asarray(u), np.asarray(i),
                np.asarray(v).astype(np.float32),
            )
            self.loss_sample_n = int(len(v))
        self._loss_dev = None  # device copies, staged on first use

    def sweep_loss(self, U, V) -> Optional[float]:
        """Per-sweep training RMSE over the retained (sub)sampled COO
        (``_sq_err_sum`` — same math as :func:`rmse`; exact when the
        dataset fits :attr:`LOSS_SAMPLE_MAX`).  The sample is staged to
        the device ONCE and reused every sweep."""
        if self._loss_coo is None:
            return None
        if self._loss_dev is None:
            u, i, v = self._loss_coo
            if self.mesh is not None:
                put = lambda x: jax.device_put(  # noqa: E731
                    x, replicated(self.mesh))
            else:
                put = jnp.asarray
            self._loss_dev = (put(u), put(i), put(v))
        ud, idv, vd = self._loss_dev
        n = self.loss_sample_n
        return math.sqrt(float(_sq_err_sum(U, V, ud, idv, vd)) / n)

    def _build_sharded_halves(self) -> None:
        cfg = self.cfg
        self.coded = bool(cfg.coded_shards) and self.sharded
        common = dict(
            implicit=cfg.implicit,
            weighted_lambda=cfg.weighted_lambda,
            precision=cfg.matmul_precision,
            solver=cfg.solver,
            gather_mode=cfg.gather_mode,
            solver_mode=cfg.solver_mode,
            subspace_size=cfg.subspace_size,
            coded=self.coded,
        )
        self._sharded_user_half = build_sharded_half(
            self.mesh, ks=self._user_side["ks"], **common
        )
        self._sharded_item_half = build_sharded_half(
            self.mesh, ks=self._item_side["ks"], **common
        )
        if self.coded:
            from ..parallel.coded import ShardHealth, build_parity_fn

            self._parity_fn = build_parity_fn(self.mesh)
            self._parity_state: dict[str, jax.Array] = {}
            # one health tracker per trainer: a worker_kill is sticky
            # across run() calls, the way a dead host stays dead
            self.shard_health = ShardHealth(
                self.mesh.size,
                hop_budget_s=cfg.shard_hop_budget_s or None,
                op="als.half",
            )

    @classmethod
    def distributed(
        cls,
        local_ratings,
        n_users: Optional[int] = None,
        n_items: Optional[int] = None,
        cfg: ALSConfig = ALSConfig(factor_placement="sharded"),
        mesh: Optional[Mesh] = None,
        exchange_dir=None,
        tag: str = "als-coo",
        timeout: float = 120.0,
    ) -> "ALSTrainer":
        """Multi-host sharded-COO trainer.

        Each process contributes only its LOCAL rating triples (e.g. the
        entity-hash shard a `find_columnar_sharded` scan returned,
        encoded against the global id index); the triples are exchanged
        so every device receives exactly the slices of the bucket rows
        it solves (`parallel/ingest.exchange_ratings_by_owner`).  **No
        process ever materializes the full COO** — the round-2
        `gather_ratings` all-gather is gone from this path, completing
        the scaling story: factor tables shard over mesh HBM, the rating
        matrix shards over mesh HBM, and the host-side COO shards over
        cluster memory (the role HBase region-sharding played for the
        reference, `storage/hbase/HBPEvents.scala:99-105`).

        Only per-row COUNT vectors (a few hundred KB at ML-20M scale)
        are all-gathered, to give every process the identical global
        bucket plan.  Single-process (or no real mesh) falls back to the
        ordinary constructor.
        """
        import jax

        if isinstance(local_ratings, Ratings):
            u, i, v = (
                local_ratings.user_ix,
                local_ratings.item_ix,
                local_ratings.rating,
            )
            n_users = local_ratings.n_users
            n_items = local_ratings.n_items
        else:
            u, i, v = local_ratings
            assert n_users is not None and n_items is not None
        if jax.process_count() <= 1 or mesh is None or mesh.size <= 1:
            return cls((u, i, v), n_users, n_items, cfg, mesh=mesh)
        if cfg.factor_placement != "sharded":
            raise ValueError(
                "ALSTrainer.distributed requires "
                "factor_placement='sharded' (the sharded-COO layout)"
            )
        if exchange_dir is None:
            raise ValueError("exchange_dir is required for multi-process")

        from jax.experimental import multihost_utils

        self = cls.__new__(cls)
        self.cfg = cfg
        self.mesh = mesh
        self.n_users = n_users
        self.n_items = n_items
        n_dev = mesh.size
        self.sharded = True
        self.staging = "sharded-distributed"
        self._pad_users = pad_to_multiple(n_users, n_dev)
        self._pad_items = pad_to_multiple(n_items, n_dev)

        def global_counts(rows, n_pad):
            local = np.bincount(rows, minlength=n_pad).astype(np.int64)
            return np.asarray(
                multihost_utils.process_allgather(local)
            ).reshape(jax.process_count(), n_pad).sum(axis=0)

        device_proc = np.asarray(
            [d.process_index for d in mesh.devices.reshape(-1)], np.int32
        )
        self._user_side = self._stage_side_distributed(
            u, i, v, global_counts(u, self._pad_users), self._pad_users,
            n_dev, device_proc, exchange_dir, f"{tag}-user", timeout,
        )
        self._item_side = self._stage_side_distributed(
            i, u, v, global_counts(i, self._pad_items), self._pad_items,
            n_dev, device_proc, exchange_dir, f"{tag}-item", timeout,
        )
        self._build_sharded_halves()
        self._plan_solves()
        self._plan_exchange()
        self._plan_gram_counters()
        # distributed staging holds only LOCAL triples; a global
        # training loss is not computable from one process
        self.loss_every = 0
        self._loss_coo = None
        self._loss_dev = None
        self.loss_sample_n = 0
        return self

    def _stage_side_distributed(
        self, rows, cols, vals, counts, n_rows_pad, n_dev, device_proc,
        exchange_dir, tag, timeout,
    ):
        """One side's sharded staging from process-LOCAL triples.

        Every process derives the identical global bucket/shard plan from
        the (all-gathered) count vector, exchanges its triples to each
        row's owning process, and builds shard arrays only for its own
        addressable devices — assembled into the global sharded array
        with ``make_array_from_single_device_arrays``.
        """
        import jax

        from ..parallel.ingest import exchange_ratings_by_owner

        cfg = self.cfg
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        buckets = _assemble_buckets(
            counts, starts, n_rows_pad, cfg.min_bucket_k,
            cfg.max_ratings_per_row, batch_multiple=n_dev,
            starts_dtype=np.int64, **self._chunk_caps(n_dev),
        )
        _, local_starts, L = _plan_shard_layout(
            buckets, n_dev, build_perm=False
        )
        # row -> owning device / shard-local start / effective cap
        row_dev = np.zeros(n_rows_pad, np.int32)
        row_ls = np.zeros(n_rows_pad, np.int64)
        row_cap = np.zeros(n_rows_pad, np.int64)
        for b, ls in zip(buckets, local_starts):
            chunk = len(b.rows) // n_dev
            real = b.rows < n_rows_pad
            rr = b.rows[real]
            row_dev[rr] = (np.arange(len(b.rows))[real] // chunk).astype(
                np.int32
            )
            row_ls[rr] = ls[real]
            row_cap[rr] = b.counts[real]

        rows2, cols2, vals2 = exchange_ratings_by_owner(
            rows, cols, vals, device_proc[row_dev], exchange_dir, tag,
            timeout=timeout,
        )
        # deterministic within-row order (sources may interleave): sort
        # by (row, col); occurrence index beyond the per-row cap
        # (max_ratings_per_row) is dropped.  NOTE the retained subset
        # under a cap is deterministic but (row, col)-ordered, whereas a
        # single-process train keeps the first cap entries in scan
        # order — with hash-sharded multi-process scans no global scan
        # order exists to reproduce, so capped distributed trains are
        # reproducible against themselves, not bit-equal to a
        # single-process capped train
        order = np.lexsort((cols2, rows2))
        rows2, cols2, vals2 = rows2[order], cols2[order], vals2[order]
        rc = np.bincount(rows2, minlength=n_rows_pad).astype(np.int64)
        rstart = np.concatenate(([0], np.cumsum(rc)[:-1]))
        occ = np.arange(len(rows2), dtype=np.int64) - rstart[rows2]
        keep = occ < row_cap[rows2]
        rows2, cols2, vals2, occ = (
            rows2[keep], cols2[keep], vals2[keep], occ[keep]
        )
        slot = (row_ls[rows2] + occ).astype(np.int64)
        dev_of = row_dev[rows2]

        pid = jax.process_index()
        mesh_devs = list(self.mesh.devices.reshape(-1))
        sh = NamedSharding(self.mesh, P(DATA_AXIS))
        c_parts, v_parts = [], []
        for di, d in enumerate(mesh_devs):
            if d.process_index != pid:
                continue
            sel = dev_of == di
            c_loc = np.zeros(L, np.int32)
            v_loc = np.zeros(L, np.float32)
            c_loc[slot[sel]] = cols2[sel]
            v_loc[slot[sel]] = vals2[sel]
            c_parts.append(jax.device_put(c_loc, d))
            v_parts.append(jax.device_put(v_loc, d))
        c_g = jax.make_array_from_single_device_arrays(
            (n_dev * L,), sh, c_parts
        )
        v_g = jax.make_array_from_single_device_arrays(
            (n_dev * L,), sh, v_parts
        )
        return self._stage_chunk_groups(
            (c_g, v_g), L, buckets, local_starts, n_rows_pad)

    def _stage_device(self, u, i, v, nu, ni, n_dev):
        """Compact-transfer staging: host counting-sort once, expand the
        opposite side **on device**.

        The host path transfers two full sorted copies of the COO
        (``[nnz]`` ids + values per side — 320 MB for ML-20M at f32/int32).
        Here the host counting-sorts the COO by user ONCE (O(n) native
        C++, `native/bucketize.cpp`; NumPy fallback) and only
        ``(item_ids, values)`` in transfer order cross the host↔device
        link, in the narrowest lossless dtypes (uint16 ids when the id
        space fits, uint8 half-star rating codes — ~60 MB for ML-20M,
        5x less than the host path, 2.3x less than round 3's raw-COO
        transfer): the user-id column is never transferred at all.  On
        device the user side's grouping IS the transfer order (zero
        work), user ids are reconstructed from the per-row counts
        (``repeat``), and the item side is one argsort + gathers.
        Each side's columns then go through :meth:`_stage_side`, which
        expands them to the padded blocks the sweeps read and drops
        them.

        The TPU lesson generalizes: host↔device bytes are the scarce
        resource (PCIe, or worse a DCN hop), device sort is cheap
        — and bytes you can DERIVE device-side are cheaper still.
        """
        if len(v) >= np.iinfo(np.int32).max:
            # same int32-offset ceiling as build_bucket_layout: starts and
            # gather positions would wrap
            raise ValueError(
                f"{len(v):,} ratings exceed the int32 offset range of a "
                "single bucket layout; shard the COO across hosts first"
            )
        # the host path's counting sort validates id ranges; match it here
        # BEFORE the uint16 compaction can wrap an oversized id silently
        if len(v):
            u = np.asarray(u)
            i = np.asarray(i)
            if int(u.min()) < 0 or int(u.max()) >= self.n_users:
                raise ValueError(
                    f"user ids must be in [0, {self.n_users}); "
                    f"got [{int(u.min())}, {int(u.max())}]"
                )
            if int(i.min()) < 0 or int(i.max()) >= self.n_items:
                raise ValueError(
                    f"item ids must be in [0, {self.n_items}); "
                    f"got [{int(i.min())}, {int(i.max())}]"
                )
        from ..native import sort_coo_by_row

        # one O(n) host counting sort by user; its counts/starts feed
        # the user-side bucket plan directly
        i_by_u, v_by_u, counts_u, starts_u = sort_coo_by_row(
            np.asarray(u, np.int32), np.asarray(i, np.int32),
            np.asarray(v, np.float32), nu,
        )
        counts_i = np.bincount(i, minlength=ni).astype(np.int32)
        starts_i = np.concatenate(
            ([0], np.cumsum(counts_i)[:-1])
        ).astype(np.int32)

        def compact_ids(x, n):
            return x.astype(np.uint16) if n <= (1 << 16) else \
                np.ascontiguousarray(x, dtype=np.int32)

        twice = v_by_u * 2.0
        half_star = (
            v_by_u.size > 0
            and float(v_by_u.min(initial=0.0)) >= 0.0
            and float(v_by_u.max(initial=0.0)) <= 127.5
            and bool(np.all(twice == np.round(twice)))
        )
        v_enc = twice.astype(np.uint8) if half_star else v_by_u
        v_scale = 0.5 if half_star else 1.0

        if self.mesh is not None:
            put = lambda x: jax.device_put(x, replicated(self.mesh))  # noqa: E731
        else:
            put = jax.device_put
        i_enc = compact_ids(i_by_u, ni)
        counts_enc = np.asarray(counts_u, np.int32)
        # observability: the bytes this path actually moves host->device
        # (the claim the on-chip battery checks; buckets are ~KB noise)
        self.staged_transfer_bytes = (
            i_enc.nbytes + v_enc.nbytes + counts_enc.nbytes
        )
        i_dev = put(i_enc)
        v_dev = put(v_enc)
        counts_dev = put(counts_enc)
        scale = jnp.asarray(v_scale, jnp.float32)
        cs_u, vs_u, cs_i, vs_i = _device_expand_sides(
            i_dev, v_dev, counts_dev, scale
        )
        # each array goes as soon as its last reader has run: the blocks
        # (8 bytes a padded entry) and both sides' columns together
        # would be this path's peak
        del i_dev, v_dev
        # the item side's columns hold each item's users in ascending
        # order (a stable sort of the user-ordered COO)
        slots = self._dense_slots(
            counts_u, counts_i, lambda: (cs_i, vs_i, put(starts_i)))
        cfg = self.cfg
        caps = self._chunk_caps(n_dev)
        buckets_u = _assemble_buckets(
            np.asarray(counts_u, np.int32), np.asarray(starts_u, np.int32),
            nu, cfg.min_bucket_k, cfg.max_ratings_per_row,
            batch_multiple=n_dev, **caps, **self._dense_caps(ni, slots),
        )
        buckets_i = _assemble_buckets(
            counts_i, starts_i, ni, cfg.min_bucket_k,
            cfg.max_ratings_per_row, batch_multiple=n_dev, **caps,
            **self._dense_caps(nu, slots),
        )
        user_side = self._stage_side(cs_u, vs_u, buckets_u, ni, slots)
        del cs_u, vs_u
        return user_side, self._stage_side(cs_i, vs_i, buckets_i, nu, slots)

    def _stage(self, layout: BucketLayout, n_opposite: int, slots):
        """Transfer the sorted COO + bucket index vectors to the device."""
        return self._stage_side(
            layout.col_sorted, layout.val_sorted, layout.buckets, n_opposite,
            slots,
        )

    def _stage_side(self, c_sorted, v_sorted, buckets, n_opposite, slots):
        """Place one side's arrays (host or already on the device) and
        expand every bucket, once, to what the sweeps read: a gathered
        bucket to its padded block, a dense one to its counts and
        ratings over all ``n_opposite`` opposite rows in ``slots``
        (`_dense_slots`).  The columns are not read again and are
        dropped here."""
        if self.mesh is not None:
            def put(*spec):
                sharding = NamedSharding(self.mesh, P(*spec))
                return lambda x: jax.device_put(x, sharding)

            put_rep, put_dp = put(), put(DATA_AXIS)
            put_rows_dp = put(None, DATA_AXIS, None)
            put_chunks_dp = put(None, DATA_AXIS)
        else:
            put_rep = put_dp = put_rows_dp = jnp.asarray
        if self.sweeps_blocks:
            # the block sweep's chunks are bounded by the bytes they
            # gather, hundreds of them at a high rank: a run of n chunks
            # of one shape is staged as ONE bucket of n * B rows, expanded
            # in one piece and laid out [n, B, ...], which
            # `_block_sweep_half` solves as one loop; the full solve's
            # buckets stay as they are, one unrolled step each
            runs = _chunk_groups(buckets)
            buckets = [
                Bucket(k=buckets[run[0]].k, **{
                    field: np.concatenate(
                        [getattr(buckets[j], field) for j in run])
                    for field in ("rows", "starts", "counts")})
                for run in runs
            ]
            chunks = [len(run) for run in runs]
        else:
            chunks = [1] * len(buckets)
        # `_assemble_buckets` puts the dense chunks last
        gathered = [b for b in buckets if b.k != DENSE_K]
        dense = buckets[len(gathered):]
        counts = [put_dp(b.counts) for b in buckets]
        columns = jax.block_until_ready(
            (put_rep(c_sorted), put_rep(v_sorted))
        )
        t0 = time.perf_counter()
        padded = jax.block_until_ready(_expand_side(
            *columns,
            tuple((put_dp(b.starts), n) for b, n in zip(gathered, counts)),
            ks=tuple(b.k for b in gathered),
        ))
        resident = jax.block_until_ready([
            _dense_chunk(columns, b, dense_blocks(n_opposite), put_rep,
                         slots)
            for b in dense
        ])
        expand_s = time.perf_counter() - t0
        blocks = [tuple(map(put_dp, blk)) for blk in padded] \
            + [tuple(map(put_rows_dp, blk)) for blk in resident]

        def by_chunk(a, n):
            if n == 1:
                return a
            a = a.reshape(n, a.shape[0] // n, *a.shape[1:])
            return put_chunks_dp(a) if self.mesh is not None else a

        return {
            "ks": tuple(b.k for b in buckets),
            "buckets": tuple(
                tuple(by_chunk(a, n) for a in (put_dp(b.rows), idx, val, m))
                for b, (idx, val), m, n in zip(buckets, blocks, counts,
                                               chunks)
            ),
            **_expansion_report(padded, expand_s),
            "entries": _gram_entries(buckets),
            "dense_rows": sum(int((b.counts > 0).sum()) for b in dense),
            "dense_bytes": sum(a.nbytes for blk in resident for a in blk),
            "dense_chunks": len(dense),
            "dense_rating_dtype": (str(resident[0][1].dtype) if resident
                                   else None),
        }

    def _stage_side_sharded(self, layout: BucketLayout, n_dev: int):
        """Place one side's COO SHARDED: device ``d`` receives only the
        rating slices of the bucket rows it solves, in shard-local order
        (``_plan_shard_layout``).  The per-bucket starts arrays are the
        shard-LOCAL offsets, so the expansion that follows
        (:meth:`_stage_chunk_groups`) indexes each device's own shard.
        """
        perm, local_starts, L = _plan_shard_layout(layout.buckets, n_dev)
        flat = perm.reshape(-1)
        c_sh = np.ascontiguousarray(layout.col_sorted[flat])
        v_sh = np.ascontiguousarray(layout.val_sorted[flat])
        from ..parallel.mesh import shard_put

        # shard_put, not device_put: a caller may hand the full COO to
        # every process of a multi-process mesh (e.g. a sharded sweep
        # after a replicated-path read); device_put would reject the
        # non-addressable devices
        put_dp = lambda x: shard_put(x, self.mesh, P(DATA_AXIS))  # noqa: E731
        return self._stage_chunk_groups(
            (put_dp(c_sh), put_dp(v_sh)), L, layout.buckets, local_starts,
            layout.n_rows)

    def _stage_chunk_groups(self, columns, shard_len: int, buckets,
                            local_starts, table_rows: int) -> dict:
        """A sharded side from its columns on the mesh (``P('data')``,
        ``shard_len`` entries a device): the chunks of one shape
        (:func:`_chunk_groups`) stacked as ``[n, B]`` arrays of rows and
        counts, each chunk's batch split over the mesh, and every group
        expanded, once, on the devices that hold the shards, to the
        ``[n, B, K]`` ids and ratings the halves read
        (`_expand_side_sharded`).  A group is the replicated path's own
        ``(rows, idx, val, counts)``; the columns and the shard-local
        starts are not read again and are dropped here.  Beside each
        group, ``owners``: the lists by which a shard of the table of
        ``table_rows`` rows writes back its own solved rows
        (`_owner_lists`), ``[n, d, cap]``, a shard its own."""
        from ..parallel.mesh import shard_put

        runs = _chunk_groups(buckets)
        n_dev = self.mesh.size

        def put(array, spec=P(None, DATA_AXIS)):
            return shard_put(array, self.mesh, spec)

        def by_run(per_chunk):
            return [np.stack([per_chunk[j] for j in run]) for run in runs]

        ks = tuple(buckets[run[0]].k for run in runs)
        ids = by_run([b.rows for b in buckets])
        owners = tuple(
            tuple(put(a, P(None, DATA_AXIS, None))
                  for a in _owner_lists(group, table_rows // n_dev, n_dev))
            for group in ids
        )
        rows = [put(group) for group in ids]
        counts = [put(g) for g in by_run([b.counts for b in buckets])]
        starts = [put(g) for g in by_run(local_starts)]
        columns = jax.block_until_ready(columns)
        t0 = time.perf_counter()
        padded = jax.block_until_ready(_expand_side_sharded(
            *columns, tuple(zip(starts, counts)), mesh=self.mesh, ks=ks,
        ))
        expand_s = time.perf_counter() - t0
        return {
            "shard_len": shard_len,
            "ks": ks,
            "buckets": tuple(
                (r, idx, val, m)
                for r, (idx, val), m in zip(rows, padded, counts)
            ),
            "owners": owners,
            **_expansion_report(padded, expand_s),
            "entries": _gram_entries(buckets),
        }

    @property
    def coo_shard_entries(self) -> Optional[int]:
        """Per-device padded rating-slot count of the sharded COO layout
        (the HBM-scaling observable: ~nnz/mesh_size + padding), or None
        when the COO is replicated.  Public accessor — demos and
        capacity planners should use this, not the staging internals."""
        if not self.sharded:
            return None
        return int(self._user_side["shard_len"])

    def init_factors(self) -> tuple[jax.Array, jax.Array]:
        """MLlib-style init: N(0, 1)/sqrt(rank), fixed seed.

        Sharded placement pads the row dim to the mesh size with ZERO rows
        (never solved; zeros keep the implicit-mode Gram matrix exact) and
        places each table ``P('data', None)``.
        """
        cfg = self.cfg
        key = jax.random.PRNGKey(cfg.seed)
        ku, ki = jax.random.split(key)
        dtype = jnp.float32
        U = jax.random.normal(ku, (self.n_users, cfg.rank), dtype)
        U = U / jnp.sqrt(cfg.rank).astype(dtype)
        V = jax.random.normal(ki, (self.n_items, cfg.rank), dtype)
        V = V / jnp.sqrt(cfg.rank).astype(dtype)
        if self.sharded:
            from ..parallel.mesh import shard_put

            U = jnp.pad(U, ((0, self._pad_users - self.n_users), (0, 0)))
            V = jnp.pad(V, ((0, self._pad_items - self.n_items), (0, 0)))
            spec = P(DATA_AXIS, None)
            # shard_put handles meshes spanning processes (device_put
            # rejects non-addressable shardings)
            return (
                shard_put(np.asarray(U), self.mesh, spec),
                shard_put(np.asarray(V), self.mesh, spec),
            )
        if self.mesh is not None:
            U = jax.device_put(U, replicated(self.mesh))
            V = jax.device_put(V, replicated(self.mesh))
        return U, V

    def _coded_parity(self, name: str, table: jax.Array) -> jax.Array:
        """Replicated parity block of ``table``, cached under ``name``
        ("user"/"item"); refreshed by each coded half's returned parity
        and reset per :meth:`run` (fresh iterates mean fresh parity)."""
        p = self._parity_state.get(name)
        if p is None:
            p = self._parity_fn(table)
            self._parity_state[name] = p
        return p

    @staticmethod
    def _sharded_operands(side: dict) -> list:
        """What a sharded half is handed after its tables and scalars:
        group by group, the staged ``(rows, idx, val, counts)`` and the
        owners' lists ``(own_pos, own_row)``."""
        return [a for group in zip(side["buckets"], side["owners"])
                for part in group for a in part]

    def _half(self, upd, opp, side, lam: Optional[float] = None) -> jax.Array:
        cfg = self.cfg
        lam_t = jnp.asarray(cfg.lam if lam is None else lam, jnp.float32)
        if self.sharded:
            fn = (
                self._sharded_user_half
                if side is self._user_side
                else self._sharded_item_half
            )
            flat = self._sharded_operands(side)
            if self.coded:
                upd_name = (
                    "user" if side is self._user_side else "item"
                )
                opp_name = "item" if upd_name == "user" else "user"
                # consult the dist.* fault points / hop budget BEFORE
                # dispatch: a late shard is served from parity, a
                # killed one stays frozen (parallel/coded.ShardHealth)
                ok = self.shard_health.poll()
                new, new_par = fn(
                    upd, opp,
                    self._coded_parity(opp_name, opp),
                    jnp.asarray(ok, jnp.float32),
                    lam_t,
                    jnp.asarray(cfg.alpha, jnp.float32),
                    *flat,
                )
                self._parity_state[upd_name] = new_par
                return new
            return fn(
                upd, opp, lam_t, jnp.asarray(cfg.alpha, jnp.float32), *flat,
            )
        return _half_iteration(
            upd, opp, side["buckets"], lam_t,
            jnp.asarray(cfg.alpha, jnp.float32),
            ks=side["ks"],
            implicit=cfg.implicit,
            weighted_lambda=cfg.weighted_lambda,
            precision=cfg.matmul_precision,
            solver=cfg.solver,
            gather_mode=cfg.gather_mode,
            solver_mode=cfg.solver_mode,
            subspace_size=cfg.subspace_size,
            mesh=self.mesh,
        )

    def _traced_half(self, upd, opp, side, side_name: str, it: int,
                     lam: Optional[float],
                     collect: Optional[dict] = None) -> jax.Array:
        """One half-iteration with pio-obs phase spans (als.gather /
        als.gram / als.solve), attributed by probe subtraction: time the
        gather-only truncation, the gather+Gram truncation, and the full
        half, each to completion; the deltas are the per-phase device
        times (ALX §5: per-phase timing is what makes TPU factorization
        tunable).  Sharded placement has no probe entry point — it
        records the completed full half as ``als.half``.

        ``collect`` (pio-tower) accumulates the emitted phase times as
        side-qualified keys (``user.gather`` ...) for the run
        manifest's sweep record.
        """
        from ..obs import get_tracer

        tracer = get_tracer()
        attrs = {"side": side_name, "iteration": it}

        def timed(fn, warm: bool):
            if warm:
                # compile outside the measured span
                jax.block_until_ready(fn())
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            return out, time.perf_counter() - t0

        def emit(phase: str, dt: float) -> None:
            tracer.record(phase, dt, attrs=attrs)
            TRAIN_PHASE_SECONDS.labels(phase=phase).observe(dt)
            if collect is not None:
                key = f"{side_name}.{phase.rsplit('.', 1)[-1]}"
                collect[key] = collect.get(key, 0.0) + dt

        if self.sharded:
            new, t_full = timed(
                lambda: self._half(upd, opp, side, lam=lam), warm=False
            )
            emit("als.half", t_full)
            return new

        cfg = self.cfg
        lam_t = jnp.asarray(cfg.lam if lam is None else lam, jnp.float32)
        alpha_t = jnp.asarray(cfg.alpha, jnp.float32)

        def probe(stop):
            return _half_phase_probe(
                upd, opp, side["buckets"], lam_t, alpha_t,
                ks=side["ks"], implicit=cfg.implicit,
                weighted_lambda=cfg.weighted_lambda,
                precision=cfg.matmul_precision, solver=cfg.solver,
                gather_mode=cfg.gather_mode,
                solver_mode=cfg.solver_mode,
                subspace_size=cfg.subspace_size,
                stop_after=stop,
            )

        # the probes must run BEFORE the real half: it donates ``upd``
        warm = it == 0
        _, t_gather = timed(lambda: probe("gather"), warm)
        _, t_gram_cum = timed(lambda: probe("gram"), warm)
        new, t_full = timed(
            lambda: self._half(upd, opp, side, lam=lam), warm=False
        )
        emit("als.gather", t_gather)
        emit("als.gram", max(t_gram_cum - t_gather, 0.0))
        emit("als.solve", max(t_full - t_gram_cum, 0.0))
        return new

    def run(
        self,
        U: jax.Array,
        V: jax.Array,
        num_iterations: int,
        lam: Optional[float] = None,
        donate: bool = False,
    ) -> tuple[jax.Array, jax.Array]:
        """Iterate; treats U/V functionally (the caller's arrays survive).

        The half-iterations donate their working buffers, so copy the
        inputs once up front — two [N, R] copies are noise next to one
        half-iteration, and callers keep usable arrays for warm restarts.
        ``donate=True`` is for the caller that gives its arrays up (a
        loop of ``U, V = run(U, V, 1, donate=True)``): the halves then
        work on the caller's own buffers, which are gone when this
        returns, and no second copy of the tables ever exists — the
        difference between fitting and not where the tables are most
        of the mesh's memory.

        ``lam`` overrides the config's regularization for THIS run: λ is
        a traced scalar, so sweeping it reuses the compiled executables
        and the staged (possibly sharded) COO — the sweep path for
        problems too big for the vmapped ``sweep_train_als``.
        """
        from ..resilience import faults

        if not donate:
            U = jnp.array(U, copy=True)
            V = jnp.array(V, copy=True)
        if self.coded:
            # fresh iterates mean the cached parity blocks are stale:
            # recompute lazily from THESE tables on first use
            self._parity_state = {}
        trace_phases = _als_phase_trace_enabled()
        session = tower.active_session()
        if session is not None:
            # the workflow layer opened the session without knowing the
            # algorithm's iteration budget; declare it for the ETA
            session.set_sweeps_planned(self.cfg.num_iterations)
        for it in range(num_iterations):
            t_sweep = time.perf_counter()
            phases: dict[str, float] = {}
            if trace_phases:
                # half-iteration granularity (probe subtraction):
                # opt-in via PIO_TPU_TRACE_ALS=1 — the probes re-run
                # truncated halves, overhead the always-on path refuses
                U = self._traced_half(U, V, self._user_side, "user", it,
                                      lam, collect=phases)
                V = self._traced_half(V, U, self._item_side, "item", it,
                                      lam, collect=phases)
            else:
                # always-on sweep telemetry: one wait per half gives
                # the user/item split with zero extra device work (the
                # halves are data-dependent, so the device pipeline
                # loses nothing; only host dispatch-ahead is traded)
                t0 = time.perf_counter()
                with annotate("pio.als.user_half"):
                    U = jax.block_until_ready(
                        self._half(U, V, self._user_side, lam=lam))
                phases["user_half"] = time.perf_counter() - t0
                t0 = time.perf_counter()
                with annotate("pio.als.item_half"):
                    V = jax.block_until_ready(
                        self._half(V, U, self._item_side, lam=lam))
                phases["item_half"] = time.perf_counter() - t0
            # booking the sweep's metrics is a phase of its own: a small
            # table's sweep is a millisecond, of which the booking is
            # some fifty microseconds
            t0 = time.perf_counter()
            if not trace_phases:
                TRAIN_PHASE_SECONDS.labels(phase="als.user_half").observe(
                    phases["user_half"]
                )
                TRAIN_PHASE_SECONDS.labels(phase="als.item_half").observe(
                    phases["item_half"]
                )
            for counter, systems in self._solve_counters:
                counter.inc(systems)
            for side_name, received in self.exchange_bytes.items():
                if received:
                    ALS_EXCHANGE_BYTES_TOTAL.labels(side=side_name).inc(
                        received)
            for side_name, written in self.write_rows.items():
                if written:
                    ALS_WRITE_ROWS_TOTAL.labels(side=side_name).inc(written)
            for side_name, gathered in self.gather_bytes.items():
                if gathered:
                    ALS_GATHER_BYTES_TOTAL.labels(side=side_name).inc(
                        gathered)
            for counter, entries in self._gram_entry_counters:
                counter.inc(entries)
            phases["counters"] = time.perf_counter() - t0
            if faults.fired("train.nan"):
                # poison the iterates the way an exploding sweep would;
                # the convergence watchdog must catch it THIS sweep
                U = U * jnp.asarray(float("nan"), U.dtype)
            loss = None
            if self.loss_every and (it + 1) % self.loss_every == 0:
                t0 = time.perf_counter()
                loss = self.sweep_loss(U, V)
                if loss is not None:
                    phases["loss"] = time.perf_counter() - t0
            finite = True
            if session is not None and session.wants_finite_check():
                t0 = time.perf_counter()
                finite = bool(_finite_all(U, V))
                phases["check"] = time.perf_counter() - t0
            # may raise tower.ConvergenceError — the typed watchdog
            # abort propagates out of the training run with the
            # manifest already finalized
            tower.record_sweep(
                time.perf_counter() - t_sweep, phases,
                loss=loss, factors_finite=finite, source=id(self),
            )
            logger.debug("ALS iteration %d/%d complete", it + 1,
                         num_iterations)
        # callers time this call: return completed arrays
        return jax.block_until_ready((U, V))

    def train(
        self,
        checkpointer=None,
        checkpoint_every: int = 5,
        resume: bool = True,
    ) -> ALSFactors:
        """Full run; with a :class:`~predictionio_tpu.workflow.checkpoint.
        StepCheckpointer`, factor state is saved every ``checkpoint_every``
        iterations and a crashed run resumes from the latest step (the
        reference reruns failed training from scratch)."""
        if checkpointer is not None and checkpoint_every <= 0:
            raise ValueError("checkpoint_every must be positive")
        U, V = self.init_factors()
        if checkpointer is None:
            # one call keeps the 2*num_iterations dispatches async
            U, V = self.run(U, V, self.cfg.num_iterations, donate=True)
            return self._factors(U, V)
        start = 0
        if resume:
            latest = checkpointer.latest_step()
            if latest is not None:
                state = checkpointer.restore(latest, like={"U": U, "V": V})
                U, V = state["U"], state["V"]
                start = latest
                logger.info("resuming ALS from iteration %d", start)
        it = start
        while it < self.cfg.num_iterations:
            chunk = min(checkpoint_every, self.cfg.num_iterations - it)
            U, V = self.run(U, V, chunk, donate=True)
            it += chunk
            checkpointer.save(it, {"U": U, "V": V})
        return self._factors(U, V)

    def _factors(self, U, V) -> ALSFactors:
        """Host factor arrays; sharded runs drop the mesh-padding rows.

        On a multi-process mesh the trained tables span hosts; gather
        them to every process (the deploy path loads full tables — the
        reference's PAlgorithm models were likewise collected to the
        driver before persisting)."""

        def to_host(a):
            if hasattr(a, "is_fully_addressable") and not a.is_fully_addressable:
                from jax.experimental import multihost_utils

                return np.asarray(
                    multihost_utils.process_allgather(a, tiled=True)
                )
            return np.asarray(a)

        return ALSFactors(
            user_factors=to_host(U)[: self.n_users],
            item_factors=to_host(V)[: self.n_items],
        )


def train_als(
    ratings: Ratings | tuple[np.ndarray, np.ndarray, np.ndarray],
    n_users: Optional[int] = None,
    n_items: Optional[int] = None,
    cfg: ALSConfig = ALSConfig(),
    mesh: Optional[Mesh] = None,
) -> ALSFactors:
    """Run ALS to convergence budget; returns host factor arrays."""
    return ALSTrainer(ratings, n_users, n_items, cfg, mesh).train()


def sweep_train_als(
    ratings: Ratings | tuple[np.ndarray, np.ndarray, np.ndarray],
    n_users: Optional[int] = None,
    n_items: Optional[int] = None,
    cfg: ALSConfig = ALSConfig(),
    lams: Sequence[float] = (),
    mesh: Optional[Mesh] = None,
) -> list[ALSFactors]:
    """Train one model per λ candidate SIMULTANEOUSLY via ``vmap``.

    The TPU-native answer to the reference's parallel evaluation sweep
    (`MetricEvaluator.scala:183-192` scores candidates with a Scala
    parallel collection; SURVEY §2.7(4) calls for vmapped sweeps): all K
    candidates' half-iterations run as ONE batched XLA program — the
    gathers, Gram einsums, and solves get a free leading batch dim on the
    MXU, and staging/bucketing is paid once for the whole sweep instead
    of once per candidate (FastEval caches reads across candidates; this
    also fuses the compute).

    Memory scales ×K (factor tables and the per-bucket gathered blocks),
    so the VMAPPED form fits evaluation-scale problems, not the full
    ML-20M train.  The vmapped form needs replicated placement and the
    XLA solver (Pallas grids don't batch under vmap); **sharded
    placement sweeps sequentially instead** — staging and the compiled
    sharded halves are built once and reused across candidates (λ is a
    traced scalar), so the sweep composes with the sharded-COO scaling
    story at the cost of K sequential trains rather than one batched
    one.
    """
    if not lams:
        return []
    if cfg.factor_placement == "sharded":
        trainer = ALSTrainer(ratings, n_users, n_items, cfg, mesh=mesh)
        out = []
        for lam in lams:
            U0, V0 = trainer.init_factors()
            U, V = trainer.run(U0, V0, cfg.num_iterations, lam=float(lam))
            out.append(trainer._factors(U, V))
        return out
    if cfg.solver not in ("auto", "xla"):
        raise ValueError(
            "sweep_train_als (vmapped form) requires solver='auto' or "
            "'xla': a Pallas grid does not batch under vmap"
        )
    trainer = ALSTrainer(ratings, n_users, n_items, cfg, mesh=mesh)
    side_u, side_i = trainer._user_side, trainer._item_side
    K = len(lams)
    lam_arr = jnp.asarray(list(lams), jnp.float32)
    alpha = jnp.asarray(cfg.alpha, jnp.float32)

    common = dict(
        implicit=cfg.implicit, weighted_lambda=cfg.weighted_lambda,
        precision=cfg.matmul_precision, solver="xla",
        gather_mode=cfg.gather_mode,
        solver_mode=cfg.solver_mode, subspace_size=cfg.subspace_size,
    )

    def make_half(side):
        def one(upd, opp, lam):
            return _half_iteration_impl(
                upd, opp, side["buckets"], lam, alpha, ks=side["ks"],
                **common,
            )

        return xray.instrument("als.sweep_half")(
            jax.jit(jax.vmap(one, in_axes=(0, 0, 0)), donate_argnums=(0,))
        )

    half_u = make_half(side_u)
    half_i = make_half(side_i)

    U0, V0 = trainer.init_factors()
    U = jnp.broadcast_to(U0, (K, *U0.shape)) + 0.0   # materialize
    V = jnp.broadcast_to(V0, (K, *V0.shape)) + 0.0
    for _ in range(cfg.num_iterations):
        U = half_u(U, V, lam_arr)
        V = half_i(V, U, lam_arr)
    Uh, Vh = np.asarray(U), np.asarray(V)
    return [
        ALSFactors(user_factors=Uh[k], item_factors=Vh[k]) for k in range(K)
    ]


# --------------------------------------------------------------------------
# Quality metrics
# --------------------------------------------------------------------------


@jax.jit
def _finite_all(U, V):
    """Watchdog NaN/Inf sentinel: one bandwidth-bound reduction over
    both factor tables (noise next to a half-iteration's Gram work)."""
    return jnp.isfinite(U).all() & jnp.isfinite(V).all()


@xray.instrument("als.sq_err_sum")
@jax.jit
def _sq_err_sum(U, V, u, i, v):
    pred = jnp.sum(U[u] * V[i], axis=-1)
    d = pred - v
    return jnp.sum(d * d)


def rmse(
    factors: ALSFactors,
    user_ix: np.ndarray,
    item_ix: np.ndarray,
    rating: np.ndarray,
    chunk: int = 1 << 20,
) -> float:
    """RMSE over COO triples, chunked to bound device memory."""
    U = jnp.asarray(factors.user_factors)
    V = jnp.asarray(factors.item_factors)
    total = 0.0
    n = len(rating)
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        total += float(
            _sq_err_sum(
                U, V,
                jnp.asarray(user_ix[s:e]),
                jnp.asarray(item_ix[s:e]),
                jnp.asarray(rating[s:e]),
            )
        )
    return float(np.sqrt(total / max(n, 1)))
