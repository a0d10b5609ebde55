"""Batched scoring + top-k ops (the serving hot path).

Replaces the reference's predict-time cosine scan over the
``productFeatures`` RDD (`/root/reference/examples/scala-parallel-
recommendation/custom-query/src/main/scala/ALSAlgorithm.scala` predict) with
one device program per (batch of) queries and a static ``k``, so the
compiled executable is reused across requests.  Small or masked calls
are one XLA matmul + ``lax.top_k`` over the whole row (the dense path);
an unmasked call over a long catalogue scores and selects in blocks of
the item axis (the blocked path below) and never writes the ``[B, M]``
score matrix.  A query's exclusions travel as item ids (``exclude``: one
``[B, E]`` int32 array padded with -1) and are applied on the device, on
the blocked path to the gathered candidates (a short list) or to the
maxima of the listed ids' own blocks before the blocks are chosen (a
long one: a shopper's whole history).  A query's ``categories`` travel
as category NUMBERS (``allow``: ``[B, C]`` int32 beside the model's
resident bit rows, one bit an item a category): the device ORs a row's
categories into one bit an item and the scan tests it on the scores
before a block's maximum is taken, and again on the chosen blocks' items
before the select.  Only a ``whiteList`` (and what the ladders do not
hold) still needs the ``[B, M]`` additive mask and with it the dense
path.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import get_registry, xray
from .solve import pallas_interpret

__all__ = ["topk_scores", "batch_topk_scores", "batch_topk_scores_t",
           "ItemTables", "pack_rows", "patch_packed_rows", "rows_per_line",
           "topk_path", "EXCLUDE_LADDER", "exclude_width", "listed_order",
           "exclude_layout", "Allowed", "CATEGORY_SLOTS", "allow_words",
           "category_bit_rows", "grow_category_rows",
           "cosine_topk", "rerank_topk", "pow2_ceil"]

TOPK_PATH = get_registry().counter(
    "pio_topk_path_total",
    "Calls of the serving scorers (topk_scores, batch_topk_scores, "
    "batch_topk_scores_t) by the path their shapes chose: blocked "
    "(block scan, top-k over block maxima, rescoring of the chosen "
    "blocks), blocked_ids (the same with excluded ids applied to the "
    "candidates on the device), blocked_cats (the same with a row's "
    "allowed items tested as bits inside the scan), dense (matmul + top-k "
    "over the whole row, masked or not) or sharded (one shard a chip)",
    labels=("path",),
)


def pow2_ceil(x: int) -> int:
    """Next power of two >= x (min 1).

    Serving paths round batch sizes AND k up to powers of two so the
    (B, k)-keyed XLA executables stay bounded at log2 each instead of
    compiling mid-traffic for every observed value."""
    return 1 << (max(int(x), 1) - 1).bit_length()


class _Counted:
    """An instrumented scorer that also counts ``pio_topk_path_total``
    once per call (``path_of`` takes the call's arguments; without one the
    scorer is always dense).  Other attributes (``lower`` ...) are the
    wrapped callable's."""

    __slots__ = ("_fn", "_path_of", "__wrapped__")

    def __init__(self, fn, path_of=None):
        self._fn = fn
        self._path_of = path_of
        self.__wrapped__ = fn

    def __call__(self, *args, **kwargs):
        path = self._path_of(*args, **kwargs) if self._path_of else "dense"
        TOPK_PATH.labels(path=path).inc()
        return self._fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)


# xray.instrument: these three are THE serving-path executables — a
# mid-traffic recompile here (un-pow2'd k or batch) is precisely what
# the /debug/xray recompile ring exists to catch.  Their named scopes
# (`topk.scores` / `topk.select`; `topk.scan|blocks|rescore|select` on
# the blocked path) go into the HLO metadata, so a profile finds each
# stage by name whatever XLA lowers it to.
@_Counted
@xray.instrument("topk.topk_scores")
@functools.partial(jax.jit, static_argnames=("k",))
def topk_scores(query_vec: jax.Array, table: jax.Array, k: int,
                bias: jax.Array | None = None):
    """scores = table @ query_vec (+bias); returns (values, indices) top-k."""
    with jax.named_scope("topk.scores"):
        scores = table @ query_vec
        if bias is not None:
            scores = scores + bias
    with jax.named_scope("topk.select"):
        return jax.lax.top_k(scores, k)


@_Counted
@xray.instrument("topk.batch_topk_scores")
@functools.partial(jax.jit, static_argnames=("k",))
def batch_topk_scores(query_vecs: jax.Array, table: jax.Array, k: int,
                      mask: jax.Array | None = None):
    """[B, R] x [M, R] -> top-k per row; ``mask`` (additive, [B, M] or [M])
    suppresses entries (use -inf)."""
    with jax.named_scope("topk.scores"):
        scores = query_vecs @ table.T
        if mask is not None:
            scores = scores + mask
    with jax.named_scope("topk.select"):
        return jax.lax.top_k(scores, k)


# -- the blocked path: exact top-k without the [B, M] score matrix -----------
#
# The item axis is cut into blocks; a scan keeps each block's best score
# per query, lax.top_k picks the k best blocks, and only their items are
# scored again and selected from.  Exact: an item of the true top-k scores
# at least the k-th best score, so does its block's maximum, and a block
# outside the k largest maxima is beaten by k items of k other blocks.
#
# A block is `blk` items 128 apart: block b of super-block s = b // 128
# holds items s*blk*128 + (b % 128) + 128*g, g < blk.  That is what one
# lane of a [B, blk*128] score tile sees, so the scan's reduction is an
# elementwise maximum of vregs (no cross-lane work), and its output is
# lane-dense.

_LANES = 128
_BLOCK_ITEMS = (64, 32, 16, 8)   # items to a block, largest first
_RESCORE_BYTES = 16 << 20        # most bytes of candidate rows to gather
_BLOCKS_PER_K = 8                # blocked only where M >= this * k * blk
_TILE_BYTES = 4 << 20            # of the table, per grid step of the scan
_PACK_ITEMS = 1 << 18            # items pack_rows re-lays at a time

# ... with excluded ids: `TopK` over the maxima costs 4.6 times as much
# an element at 32 chosen blocks as at 16 (v5e: [64, 584,704] 8.7 ms,
# [16, 146,176] 0.41), a gathered line 8-11 ns, so larger blocks pay
_RESCORE_BYTES_IDS = 128 << 20

# Widths E of the ``[B, E]`` array of excluded ids, so that the compiled
# programs stay (pow2 B) x (pow2 k) x (these).  Every width is one more
# program a rung of the warm-up ladder (with 4 and 16 a server was 3 s
# later ready than with 16 alone; v5e), so a server warms the rungs its
# engine names (``_common.warm_batched_topk(exclude_widths=)``): the
# first alone for a blackList, all of them where a user's whole history
# is excluded.  A batch pays for its longest row's rung; one whose
# longest list is longer than the last takes a ``[B, M]`` mask.
EXCLUDE_LADDER = (32, 128, 512, 2048, 4224)

# Up to the first width `k + E` blocks are chosen and every candidate is
# compared with every id (`_blocked_topk`); past it the listed ids' own
# blocks are re-reduced and k blocks chosen (`_blocked_topk_listed`)
_PAIRWISE_EXCLUDE = EXCLUDE_LADDER[0]
# ... whose blocks' items are the bits of one uint32 a listed id
_BLOCK_ITEMS_LISTED = (32, 16, 8)


# The width of the ``[B, C]`` array of a batch's category numbers: one
# program a (B, k), so one width until a second is measured; a query that
# names more categories takes the ``[B, M]`` mask.  A row costs the
# device one resident bit row read a slot (`_allowed_words`), named or not.
CATEGORY_SLOTS = 4

# A row's allowed items are bits: bit g of the word at lane l of line
# group w is item ``(32 w + g) * 128 + l``.  The scan's score tile keeps
# items 128 apart on one lane, so the kernel tests a lane group's 128
# scores with ONE shift of a ``[B, 128]`` vector of words it holds
# anyway, whatever the block size (a block is `blk` consecutive bits of
# a lane's words).
_WORD_BITS = 32
_WORD_ITEMS = _WORD_BITS * _LANES
_WORD_LINES = 8     # lines of 128 words to one (8, 128) tile of a bit row


def exclude_width(n_excluded: int) -> int:
    """The ladder's rung for a batch whose longest list of excluded ids
    has `n_excluded` entries; 0 where it has none or the ladder ends
    below it."""
    if n_excluded <= 0:
        return 0
    return next((e for e in EXCLUDE_LADDER if n_excluded <= e), 0)


def allow_words(n_items: int) -> int:
    """uint32 words to a row of allowed bits over `n_items` items: whole
    tiles of 8 lines of 128 words, 4,096 items a line."""
    return -(-n_items // (_WORD_ITEMS * _WORD_LINES)) * _WORD_LINES * _LANES


def category_bit_rows(offsets, members, n_items: int, lo: int,
                      hi: int) -> np.ndarray:
    """``[hi - lo, allow_words(n_items)]`` uint32 (numpy, on the host):
    the bit rows of categories ``lo .. hi - 1`` of a category-major index
    (`members[offsets[c]:offsets[c + 1]]` the distinct item indices of
    category c), in the layout the scan tests."""
    n_words = allow_words(n_items)
    ids = np.asarray(members[offsets[lo]:offsets[hi]], np.int64)
    row = np.repeat(np.arange(hi - lo, dtype=np.int64),
                    np.diff(offsets[lo:hi + 1]))
    line = ids // _LANES
    words = np.zeros((hi - lo) * n_words, np.uint32)
    np.bitwise_or.at(
        words, row * n_words + (line // _WORD_BITS) * _LANES + ids % _LANES,
        np.uint32(1) << (line % _WORD_BITS).astype(np.uint32))
    return words.reshape(hi - lo, n_words)


def grow_category_rows(rows: jax.Array, n_items: int) -> jax.Array:
    """The resident bit rows (:class:`Allowed`'s `rows`) after a table has
    grown to `n_items` items: widened by whole tiles to
    ``allow_words(n_items)`` words, zero bits for every category (an
    appended item is in none) and set ones for the last row, every item.
    The same array where it is wide enough already."""
    tiles = allow_words(n_items) // (_WORD_LINES * _LANES) - rows.shape[1]
    if tiles <= 0:
        return rows
    more = np.zeros((rows.shape[0], tiles, _WORD_LINES, _LANES), np.uint32)
    more[-1] = ~np.uint32(0)
    return jnp.concatenate([rows, jnp.asarray(more)], axis=1)


class Allowed(NamedTuple):
    """A batch's categories in the form the scorer takes
    (``batch_topk_scores_t(allow=)``): `numbers` ``[B, C]`` int32, the
    category numbers a row names (-1 for an empty slot; a row of -1 alone
    allows every item), and `rows` ``[n + 2, allow_words(M) / 1024, 8,
    128]`` uint32, the model's resident bit rows (`category_bit_rows`) of
    its n categories, then a row of no item (number n: a name the model
    does not know) and a row of every item (every bit set, the words'
    padding past the catalogue too: the scan and the rescoring drop an id
    past the table whatever its bit).  A row's words by whole
    (8, 128) tiles, so that on the device a category's row is ONE
    contiguous piece whatever layout the TPU would choose for a wide
    2-D or 3-D array (it lays ``[n, W / 128, 128]`` out with the
    categories on the sublanes)."""

    numbers: jax.Array
    rows: jax.Array


class ItemTables(NamedTuple):
    """The two device layouts of one item table that the blocked path
    reads, handed to :func:`batch_topk_scores_t` in place of the
    transposed one alone: the scan streams ``t`` (``[R, M]``, items on the
    lanes), the rescoring gathers item rows from ``packed``
    (:func:`pack_rows`).  At a rank of whole lines (128, 256) ``packed``
    is the row-major ``[M, R]`` table itself and ``t`` is None: the scan
    reads the rows, because the TPU keeps ``[128, M]`` float32 with the
    short axis minor (the row-major table's own bytes) and would re-lay
    all of it for the kernel on every call."""

    t: jax.Array | None
    packed: jax.Array

    @property
    def shape(self):
        """``(rank, n_items)``, as the transposed table's."""
        return self.packed.shape[::-1] if self.t is None else self.t.shape


def rows_per_line(rank: int) -> int:
    """Item rows to one 128-lane line of the packed table; 0 where the
    rank neither divides nor is a multiple of the lane count."""
    if rank % _LANES == 0:
        return 1
    return _LANES // rank if _LANES % rank == 0 else 0


@jax.jit
def pack_rows(table: jax.Array) -> jax.Array:
    """``[M, R]`` -> ``[ceil(M / p), p * R]`` with ``p * R`` a multiple of
    128: the row-major table with p consecutive items to a line, so that
    a line is whole lanes and a gather of lines moves contiguous memory.
    The TPU keeps a narrow ``[M, 64]`` array column-major (no lane
    padding: the same bytes as its transpose), and a row gather from it,
    like a column gather from ``[R, M]``, makes XLA re-lay the whole table
    on every call.  Packing is that re-lay done once, `_PACK_ITEMS` items
    at a time: in one piece its temporaries are four times the table."""
    n_items, rank = table.shape
    p = rows_per_line(rank)
    if not p:
        raise ValueError(f"rows of rank {rank} pack into no 128-lane line")
    out = jnp.zeros((-(-n_items // p), p * rank), table.dtype)

    def put(out, part, first_line):
        return jax.lax.dynamic_update_slice_in_dim(
            out, part.reshape(part.shape[0] // p, p * rank), first_line, 0)

    n_whole = n_items // _PACK_ITEMS
    if n_whole:
        out = jax.lax.fori_loop(
            0, n_whole,
            lambda i, out: put(out, jax.lax.dynamic_slice_in_dim(
                table, i * _PACK_ITEMS, _PACK_ITEMS), i * (_PACK_ITEMS // p)),
            out)
    rest = table[n_whole * _PACK_ITEMS:]
    if rest.shape[0]:
        rest = jnp.pad(rest, ((0, -rest.shape[0] % p), (0, 0)))
        out = put(out, rest, n_whole * (_PACK_ITEMS // p))
    return out


def patch_packed_rows(packed: jax.Array, n_items: int, ixs, rows,
                      appended=None) -> jax.Array:
    """The packed table of `n_items` items with rows `ixs` set to `rows`
    and `appended` (``[A, R]`` or None) added as items ``n_items ..``:
    a scatter of the delta's elements, no rebuild (pio-live)."""
    rank = rows.shape[1]
    p = packed.shape[1] // rank
    ixs = jnp.asarray(ixs, jnp.int32)
    rows = jnp.asarray(rows)
    if appended is not None and len(appended):
        total = n_items + len(appended)
        packed = jnp.pad(
            packed, ((0, -(-total // p) - packed.shape[0]), (0, 0)))
        ixs = jnp.concatenate([ixs, jnp.arange(n_items, total,
                                               dtype=jnp.int32)])
        rows = jnp.concatenate([rows, jnp.asarray(appended)])
    lanes = (ixs % p)[:, None] * rank + jnp.arange(rank, dtype=jnp.int32)
    return packed.at[(ixs // p)[:, None], lanes].set(
        rows.astype(packed.dtype))


def block_items(batch: int, n_items: int, rank: int, k: int,
                itemsize: int = 4, n_exclude: int = 0) -> int:
    """Items to a block for a ``[batch, rank] x [rank, n_items]`` top-k
    with up to `n_exclude` excluded ids a row, or 0 where the dense form
    is the right one: the largest block whose ``batch * (k + n_exclude)``
    chosen blocks gather within the budget (`_RESCORE_BYTES`, or
    `_RESCORE_BYTES_IDS` with excluded ids), over a catalogue long
    enough that the blocks outnumber those chosen several times, at a
    rank whose rows pack into whole lanes."""
    if not rows_per_line(rank):
        return 0
    if n_exclude > _PAIRWISE_EXCLUDE:
        return _block_items_listed(batch, n_items, rank, k, itemsize,
                                   n_exclude)
    chosen = k + n_exclude
    budget = _RESCORE_BYTES_IDS if n_exclude else _RESCORE_BYTES
    for blk in _BLOCK_ITEMS:
        if batch * chosen * blk * rank * itemsize <= budget:
            return blk if n_items >= _BLOCKS_PER_K * chosen * blk else 0
    return 0


def _block_items_listed(batch: int, n_items: int, rank: int, k: int,
                        itemsize: int, n_exclude: int) -> int:
    """`block_items` for a list too long to compare pairwise: k blocks
    are chosen, and what is gathered besides is every listed id's own
    block.  The largest block within `_RESCORE_BYTES_IDS`; where not even
    the smallest is, the smallest as long as its gather stays under a
    quarter of the table the scan reads anyway (the dense form writes and
    reads ``batch`` times a twenty-eighth of it and sorts whole rows)."""
    gathered = batch * (k + n_exclude) * rank * itemsize
    blk = next((b for b in _BLOCK_ITEMS_LISTED
                if gathered * b <= _RESCORE_BYTES_IDS),
               _BLOCK_ITEMS_LISTED[-1])
    if gathered * blk > max(_RESCORE_BYTES_IDS,
                            n_items * rank * itemsize // 4):
        return 0
    return blk if n_items >= _BLOCKS_PER_K * k * blk else 0


def _blocked_items(query_vecs, table_t, k: int, mask, exclude,
                   allow=None) -> int:
    """`block_items` of a call's arguments: 0 with a ``[B, M]`` mask
    (it needs the ``[B, M]`` scores), without the packed rows (the
    rescoring gathers them), or with categories beside a list too long to
    compare pairwise (the listed form re-reduces its blocks without the
    allowed bits)."""
    if mask is not None or not isinstance(table_t, ItemTables):
        return 0
    width = 0 if exclude is None else exclude.shape[1]
    if allow is not None and width > _PAIRWISE_EXCLUDE:
        return 0
    return block_items(
        query_vecs.shape[0], *table_t.shape[::-1], k,
        table_t.packed.dtype.itemsize, width)


def topk_path(query_vecs, table_t, k: int, mask=None, exclude=None,
              allow=None) -> str:
    """``"blocked"`` or ``"dense"``: what :func:`batch_topk_scores_t`
    does with these arguments, decided from their shapes alone."""
    return ("blocked" if _blocked_items(query_vecs, table_t, k, mask,
                                        exclude, allow) else "dense")


def _counted_path(query_vecs, table_t, k: int, mask=None,
                  exclude=None, allow=None) -> str:
    """`pio_topk_path_total`'s label: the path, and on the blocked one
    whether categories or excluded ids rode along."""
    path = topk_path(query_vecs, table_t, k, mask, exclude, allow)
    if path != "blocked":
        return path
    if allow is not None:
        return "blocked_cats"
    return "blocked_ids" if exclude is not None else path


def _mxu_operands() -> bool:
    """Whether this backend's default matmul precision rounds float32
    operands to bfloat16 (the TPU's does): the scan kernel and the
    rescoring then round the same way, so both see one score per item."""
    return jax.default_backend() == "tpu"


def _block_max_kernel(q_ref, t_ref, out_ref, *, n_items: int, blk: int,
                      to_bf16: bool, row_major: bool = False,
                      allow_ref=None):
    """One tile of the transposed table: ``[B, R] x [R, TM]`` on the MXU,
    then per super-block the elementwise maximum of its `blk` lane
    groups.  The table's ragged tail (the tile's columns >= n_items hold
    whatever the DMA left there) is masked here, on the scores.  With
    `row_major` the tile is ``[TM, R]`` of the row-major table and the
    product contracts both operands' last axis (6.36 ms either way over
    9.35 M x 128; v5e).  With `allow_ref` (``[B, TM / 32]`` int32: the
    tile's allowed bits, a tile begins on a word) a lane group's scores
    are kept where their bit is set and -inf elsewhere, before the
    maximum: one shift that brings the group's bit to the sign, one
    compare, one select."""
    tm = t_ref.shape[0 if row_major else 1]
    sb = blk * _LANES
    op = jnp.bfloat16 if to_bf16 else jnp.float32
    q = q_ref[...].astype(op)
    tile0 = pl.program_id(0) * tm
    for c in range(tm // sb):
        if row_major:   # a [TM, R] tile of item rows: q x rows^T
            scores = jax.lax.dot_general(
                q, t_ref[c * sb:(c + 1) * sb, :].astype(op),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            scores = jnp.dot(q, t_ref[:, c * sb:(c + 1) * sb].astype(op),
                             preferred_element_type=jnp.float32)
        col0 = tile0 + c * sb

        def put(s):
            words = {}

            def group(g):
                x = s[:, g * _LANES:(g + 1) * _LANES]
                if allow_ref is None:
                    return x
                w, bit = divmod(c * blk + g, _WORD_BITS)
                if w not in words:
                    words[w] = allow_ref[:, w * _LANES:(w + 1) * _LANES]
                return jnp.where(
                    (words[w] << (_WORD_BITS - 1 - bit)) < 0, x, -jnp.inf)

            best = group(0)
            for g in range(1, blk):
                best = jnp.maximum(best, group(g))
            out_ref[:, c * _LANES:(c + 1) * _LANES] = best

        @pl.when(col0 + sb <= n_items)
        def _():
            put(scores)

        @pl.when(col0 + sb > n_items)
        def _():
            cols = col0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            put(jnp.where(cols < n_items, scores, -jnp.inf))


def block_maxima(query_vecs: jax.Array, table_t: jax.Array, blk: int,
                 interpret: bool | None = None,
                 row_major: bool = False,
                 allow: jax.Array | None = None) -> jax.Array:
    """The scan as one Pallas kernel: ``[B, n_blocks]`` float32, the best
    score of each block, reading the table once and writing no score
    matrix.  ``interpret=None`` follows :func:`ops.solve.pallas_interpret`.
    With `row_major` `table_t` is the ``[M, R]`` table itself.  With
    `allow` (``[B, allow_words(M)]`` uint32) a block's best score is that
    of its ALLOWED items, -inf where it holds none: the kernel reads a
    tile's 1/32 word an item beside the tile."""
    if interpret is None:
        interpret = pallas_interpret()
    batch, rank = query_vecs.shape
    n_items = table_t.shape[0 if row_major else 1]
    sb = blk * _LANES
    # with allowed bits a tile begins on a word of them
    unit = sb if allow is None else max(sb, _WORD_ITEMS)
    per_col = rank * jnp.dtype(table_t.dtype).itemsize
    tm = unit * max(1, _TILE_BYTES // per_col // unit)
    tm = min(tm, unit * pl.cdiv(n_items, unit))
    n_tiles = pl.cdiv(n_items, tm)
    rows = 8 * pl.cdiv(batch, 8)
    q = jnp.pad(query_vecs, ((0, rows - batch), (0, 0)))
    table_spec = (pl.BlockSpec((tm, rank), lambda j: (j, 0)) if row_major
                  else pl.BlockSpec((rank, tm), lambda j: (0, j)))
    kernel = functools.partial(_block_max_kernel, n_items=n_items, blk=blk,
                               to_bf16=_mxu_operands(), row_major=row_major)
    operands = [q, table_t]
    in_specs = [pl.BlockSpec((rows, rank), lambda j: (0, 0)), table_spec]
    if allow is not None:
        scores_alone = kernel

        def kernel(q_ref, t_ref, allow_ref, out_ref):
            scores_alone(q_ref, t_ref, out_ref, allow_ref=allow_ref)

        # a last tile past the words' end reads what the DMA left there:
        # those items lie past the catalogue and the kernel's tail mask
        # drops them whatever their bits say
        operands.append(jax.lax.bitcast_convert_type(
            jnp.pad(allow, ((0, rows - batch), (0, 0))), jnp.int32))
        in_specs.append(pl.BlockSpec((rows, tm // _WORD_BITS),
                                     lambda j: (0, j)))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n_tiles * tm // blk),
                                       jnp.float32),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, tm // blk), lambda j: (0, j)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=64 << 20,
        ),
        name="pio_block_max",
        interpret=interpret,
    )(*operands)
    return out[:batch]


def _allowed_items(words: jax.Array, ids: jax.Array) -> jax.Array:
    """Bool, of `ids`' shape: whether each item's bit is set in its row
    of `words` (``[B, W]`` uint32; `ids` ``[n]`` for every row alike or
    ``[B, n]``); False for an id past the words."""
    line = ids // _LANES
    at = (line // _WORD_BITS) * _LANES + ids % _LANES
    inside = at < words.shape[1]
    at = jnp.broadcast_to(jnp.where(inside, at, 0),
                          (words.shape[0], ids.shape[-1]))
    bit = (jnp.take_along_axis(words, at, axis=1)
           >> (line % _WORD_BITS).astype(jnp.uint32)) & 1
    return inside & bit.astype(bool)


def block_maxima_jnp(query_vecs: jax.Array, table_t: jax.Array, blk: int,
                     row_major: bool = False,
                     allow: jax.Array | None = None) -> jax.Array:
    """The same scan in plain ``jnp`` (the off-TPU form): the product is
    written and read back once, but no full-width top-k walks it."""
    batch = query_vecs.shape[0]
    if row_major:
        table_t = table_t.T
    n_items = table_t.shape[1]
    sb = blk * _LANES
    n_sb = -(-n_items // sb)
    scores = query_vecs @ table_t
    if allow is not None:
        scores = jnp.where(
            _allowed_items(allow, jnp.arange(n_items, dtype=jnp.int32)),
            scores, -jnp.inf)
    scores = jnp.pad(scores,
                     ((0, 0), (0, n_sb * sb - n_items)),
                     constant_values=-jnp.inf)
    return scores.reshape(batch, n_sb, blk, _LANES).max(axis=2).reshape(
        batch, n_sb * _LANES)


def _select_k(scores: jax.Array, ids: jax.Array, k: int):
    """The k best of ``[B, P]`` candidates by (score descending, id
    ascending), as k passes of a row maximum: P is k blocks, so this is
    small, and it is neither a sort nor a ``top_k`` (the batch keeps ONE
    `TopK` device op, which the benchmark's readers count)."""
    gone = jnp.iinfo(jnp.int32).max

    def step(carry, _):
        s, ix = carry
        best = s.max(axis=1, keepdims=True)
        pick = jnp.where(s == best, ix, gone).min(axis=1, keepdims=True)
        hit = ix == pick
        return ((jnp.where(hit, -jnp.inf, s), jnp.where(hit, gone, ix)),
                (best[:, 0], pick[:, 0]))

    _, (vals, ixs) = jax.lax.scan(step, (scores, ids), None, length=k)
    return vals.T, ixs.T


def _excluded(ids: jax.Array, exclude: jax.Array) -> jax.Array:
    """``[B, P]`` bool: which of a row's candidate ids are in its list
    of excluded ids (``[B, E]``; the -1 padding equals no id)."""
    return (ids[:, :, None] == exclude[:, None, :]).any(axis=-1)


def _padded_queries(query_vecs, exclude):
    """Whole sublanes of queries: the kernel wants them, and XLA then
    lowers the top_k over the maxima to a `TopK` custom call of its own
    for a one-row batch too (it wraps that of a [1, n] operand in a
    fusion, whose device event carries another name)."""
    n_queries = query_vecs.shape[0]
    batch = 8 * pl.cdiv(n_queries, 8)
    query_vecs = jnp.pad(query_vecs, ((0, batch - n_queries), (0, 0)))
    if exclude is not None:
        exclude = jnp.pad(exclude, ((0, batch - n_queries), (0, 0)),
                          constant_values=-1)
    return query_vecs, exclude


def _allowed_words(allow: Allowed, batch: int) -> jax.Array:
    """``[batch, W]`` uint32: each row's allowed items, the OR of the
    resident bit rows of the categories it names (its named slots come
    first); the rows past the batch's own (the sublane padding) and a
    row that names none allow every item.  One row of the batch at a
    time, each a read of the resident rows it names and one write of its
    own words, each one contiguous piece; then ONE re-lay of the
    batch's words to rows on the sublanes, as the scan reads them.  A
    gather of ``[B, C]`` rows this wide makes the TPU compiler copy the
    whole resident array (its plan for a described v5e: 4.3 GB of
    temporaries against 0.3 MB), and a row sliced out of a 2-D
    ``[n, W]`` array moves in 512-byte pieces, 10 ns each (51 us a row
    read and written; v5e)."""
    numbers, rows = allow
    numbers = jnp.pad(numbers, ((0, batch - numbers.shape[0]), (0, 0)),
                      constant_values=-1)
    nothing = rows.shape[0] - 2
    named = numbers >= 0
    at = jnp.where(named, numbers, nothing)
    at = at.at[:, 0].set(jnp.where(named[:, 0], at[:, 0], nothing + 1))
    several = named[:, 1:].any(axis=1)

    def read(number):
        return jax.lax.dynamic_index_in_dim(rows, number, 0, keepdims=True)

    def one(mine):
        return read(mine[0])

    def every(mine):
        return functools.reduce(jnp.bitwise_or, [
            read(mine[slot]) for slot in range(1, mine.shape[0])],
            read(mine[0]))

    def put(row, words):
        return jax.lax.dynamic_update_slice_in_dim(
            words, jax.lax.cond(several[row], every, one, at[row]), row, 0)

    words = jax.lax.fori_loop(
        0, batch, put, jnp.zeros((batch, *rows.shape[1:]), rows.dtype))
    return words.reshape(batch, -1)


def _allowed_in_blocks(words, blocks, blk: int):
    """``[B, P * blk]`` bool: item by item of the ``[B, P]`` blocks
    (`_block_item_ids`' order), whether its bit is set in the row's
    `words`.  A block's items are consecutive bits of its lane's words,
    so a block reads one word (two at 64 items), not one an item."""
    line0 = (blocks // _LANES) * blk            # the block's first line
    n_words = max(1, blk // _WORD_BITS)
    at = ((line0 // _WORD_BITS)[:, :, None]
          + jnp.arange(n_words, dtype=jnp.int32)) * _LANES \
        + (blocks % _LANES)[:, :, None]
    got = jnp.take_along_axis(
        words, jnp.minimum(at, words.shape[1] - 1).reshape(
            blocks.shape[0], -1), axis=1).reshape(*blocks.shape, n_words)
    line = line0[:, :, None] + jnp.arange(blk, dtype=jnp.int32)
    word = jnp.repeat(got, blk // n_words, axis=2)
    bit = (word >> (line % _WORD_BITS).astype(jnp.uint32)) & 1
    return bit.astype(bool).reshape(blocks.shape[0], -1)


def _scan_maxima(query_vecs, tables: ItemTables, blk: int, allow=None):
    """``[B, n_blocks]`` block maxima of the scores: the unfiltered ones,
    or with `allow` (``[B, W]`` words) those of each block's allowed
    items."""
    # the table the scan streams: transposed, or the rows themselves
    row_major = tables.t is None
    scanned = tables.packed if row_major else tables.t
    with jax.named_scope("topk.scan"):
        scan = block_maxima if _mxu_operands() else block_maxima_jnp
        return scan(query_vecs, scanned, blk, row_major=row_major,
                    allow=allow)


def _block_item_ids(blocks, blk: int):
    """``[B, P * blk]`` item ids of the ``[B, P]`` blocks, a block's
    `blk` items side by side."""
    first = (blocks // _LANES) * (blk * _LANES) + blocks % _LANES
    return (first[:, :, None]
            + _LANES * jnp.arange(blk, dtype=jnp.int32)[None, None, :]
            ).reshape(blocks.shape[0], blocks.shape[1] * blk)


def _rescore(query_vecs, tables: ItemTables, ids):
    """``[B, P]`` scores of the items `ids` at the scan's precision, from
    their gathered rows; -inf for an id past the catalogue."""
    rank, n_items = tables.shape
    inside = ids < n_items
    p = rows_per_line(rank)
    safe = jnp.where(inside, ids, 0)
    lines = tables.packed[safe // p].astype(jnp.float32)  # [B, P, p*R]
    q = jnp.tile(query_vecs.astype(jnp.float32), (1, p))  # [B, p*R]
    if _mxu_operands():
        # what the MXU's default precision does to float32 operands,
        # written as an op XLA may not drop; the products of two
        # such values are exact in float32, like the MXU's
        lines, q = (jax.lax.reduce_precision(x, 8, 7)
                    for x in (lines, q))
    prod = lines * q[:, None, :]
    if p > 1:   # the lanes of a line that are this candidate's row
        lane_row = jnp.arange(p * rank, dtype=jnp.int32) // rank
        prod = jnp.where(lane_row == (safe % p)[:, :, None], prod, 0.0)
    scores = prod.sum(axis=-1)
    return jnp.where(inside, scores, -jnp.inf)


def _blocked_topk(query_vecs, tables: ItemTables, k: int, blk: int,
                  exclude: jax.Array | None = None,
                  allow: Allowed | None = None):
    """Exact top-k of the allowed items.  A row with e excluded ids finds
    its k best allowed items among the k + e blocks with the largest
    UNMASKED maxima: a block that outranks the k-th allowed score and
    holds none of the k has an excluded item as its maximum, and there
    are at most e of those.  So the scan is the unfiltered one, ``k + E``
    blocks are chosen, and the exclusions are applied to the gathered
    candidates before the select.  With `allow` (a row's categories) a
    block's maximum is that of the items the categories allow, tested as
    bits inside the scan, and the same argument holds among those items;
    the chosen blocks' other items are dropped by the same bits before
    the select, so a row that allows fewer than k answers fewer."""
    n_queries = query_vecs.shape[0]
    n_blocks = k if exclude is None else k + exclude.shape[1]
    query_vecs, exclude = _padded_queries(query_vecs, exclude)
    words = None
    if allow is not None:
        with jax.named_scope("topk.allow_bits"):
            words = _allowed_words(allow, query_vecs.shape[0])
    maxima = _scan_maxima(query_vecs, tables, blk, words)
    with jax.named_scope("topk.blocks"):
        # ties go to the lower block index (lax.top_k is stable)
        _, chosen = jax.lax.top_k(maxima, n_blocks)
    with jax.named_scope("topk.rescore"):
        ids = _block_item_ids(chosen, blk)
        scores = _rescore(query_vecs, tables, ids)
    if words is not None:
        with jax.named_scope("topk.allow"):
            scores = jnp.where(_allowed_in_blocks(words, chosen, blk),
                               scores, -jnp.inf)
    if exclude is not None:
        with jax.named_scope("topk.exclude"):
            scores = jnp.where(_excluded(ids, exclude), -jnp.inf, scores)
    with jax.named_scope("topk.select"):
        vals, ixs = _select_k(scores, ids, k)
        return vals[:n_queries], ixs[:n_queries]


_LISTED_LANE = 1 << 24    # a listed id's place: lane * this + id // 128


def listed_order(ids) -> np.ndarray:
    """The distinct ids of a list (numpy, on the host) in the order the
    listed form reads them: by lane (``id % 128``), then by ``id // 128``.
    In that order the ids of one block lie together whatever the block's
    size, so the device sorts nothing (:func:`exclude_layout`)."""
    ids = np.asarray(ids, np.int64)
    key = np.unique((ids % _LANES) * _LISTED_LANE + ids // _LANES)
    return ((key % _LISTED_LANE) * _LANES + key // _LISTED_LANE).astype(
        np.int32)


def exclude_layout(ids, width: int):
    """A row's excluded ids as the form that reads an ids array of
    `width` wants them: as they come up to the first rung (every
    candidate is compared with every id), in :func:`listed_order` past
    it."""
    return listed_order(ids) if width > _PAIRWISE_EXCLUDE else ids


def _listed_blocks(exclude, blk: int, n_blocks: int):
    """The listed ids by block.  ``([B, E] block, [B, E] uint32 bits,
    [B] ordered)``: each listed id's block (`n_blocks`, a block past the
    last, for the -1 padding); one bit an item of that block, which of
    its items the row lists (the OR over the run of the list that the
    block is: at most `blk` entries, side by side in
    :func:`listed_order`); and whether the row IS in that order, distinct,
    its padding last."""
    listed = exclude >= 0
    e = jnp.where(listed, exclude, 0)
    q, lane = e // _LANES, e % _LANES
    key = jnp.where(listed, lane * _LISTED_LANE + q,
                    jnp.iinfo(jnp.int32).max)
    ordered = ((key[:, 1:] > key[:, :-1]) | ~listed[:, 1:]).all(axis=1)
    block = jnp.where(listed, (q // blk) * _LANES + lane, n_blocks)
    bit = jnp.where(listed, jnp.uint32(1) << (q % blk).astype(jnp.uint32),
                    jnp.uint32(0))
    bits = bit
    for d in range(1, blk):
        same = block[:, d:] == block[:, :-d]
        none = jnp.zeros((block.shape[0], d), jnp.uint32)
        bits = (bits
                | jnp.concatenate(
                    [jnp.where(same, bit[:, d:], jnp.uint32(0)), none], 1)
                | jnp.concatenate(
                    [none, jnp.where(same, bit[:, :-d], jnp.uint32(0))], 1))
    return block, bits, ordered


_SCATTER_UPDATES = 1 << 14   # a scatter of more compiles for 8-10 s (v5e)


def _put_maxima(maxima, block, values):
    """``[B, n_blocks / 128, 128]``: `maxima` by lines of 128 blocks, with
    ``[row, block[row, j]] = values[row, j]`` (a block past the last is
    dropped; the entries of one block carry one value).  Scattered into
    the FLAT array and never laid out ``[B, n_blocks]`` again: the TPU
    compiler turns a scatter into a wide 2-D operand into one over its
    flat form and back, row by row (14.7 ms for ``[16, 1.17 M]``; v5e),
    where flat and by lines are the same bytes.  In runs of at most
    `_SCATTER_UPDATES` updates, one scatter body in a loop: it takes
    8-10 s to compile a larger one."""
    batch, n_blocks = maxima.shape
    width = block.shape[1]
    rows = jnp.arange(batch, dtype=jnp.int32)[:, None]
    at = jnp.where(block < n_blocks, rows * n_blocks + block,
                   batch * n_blocks)
    chunk = max(c for c in range(1, width + 1)
                if width % c == 0 and batch * c <= _SCATTER_UPDATES)

    def put(flat, part):
        return flat.at[part[0]].set(part[1], mode="drop"), None

    by_chunk = tuple(
        x.reshape(batch, width // chunk, chunk).swapaxes(0, 1).reshape(
            width // chunk, batch * chunk) for x in (at, values))
    flat = jax.lax.scan(put, maxima.reshape(-1), by_chunk)[0]
    return flat.reshape(batch, n_blocks // _LANES, _LANES)


def _best_blocks(lines, k: int):
    """``[B, k]``: the k blocks with the largest maxima, ties to the lower
    block, from the maxima by lines of 128 (`_put_maxima`): ONE `TopK`,
    of the k best lines by their own maxima (a block of the k best lies
    in one of them), then the k best of their ``k * 128`` blocks."""
    batch, n_lines, _ = lines.shape
    _, best = jax.lax.top_k(lines.max(axis=-1), min(k, n_lines))
    maxima = jnp.take_along_axis(lines, best[:, :, None], axis=1)
    blocks = best[:, :, None] * _LANES + jnp.arange(_LANES, dtype=jnp.int32)
    return _select_k(maxima.reshape(batch, -1), blocks.reshape(batch, -1),
                     k)[1]


def _listed_items(bits, blk: int):
    """``[B, P * blk]`` bool from ``[B, P]`` bits: item by item of each
    block, whether its bit is set."""
    item = jnp.arange(blk, dtype=jnp.uint32)[None, None, :]
    return ((bits[:, :, None] >> item) & 1).astype(bool).reshape(
        bits.shape[0], bits.shape[1] * blk)


def _blocked_topk_listed(query_vecs, tables: ItemTables, k: int, blk: int,
                         exclude: jax.Array):
    """Exact top-k of the allowed items for lists of any length: the
    scan is the unfiltered one; then every listed id's own block is
    gathered and reduced again without its listed items, and that maximum
    takes the scan's place; so every block's maximum is that of its
    ALLOWED items, k blocks are chosen as without a filter, and the
    chosen blocks' listed items are dropped before the select.  No
    candidate is compared with every id: the list comes ordered by block
    (:func:`listed_order`), a block's listed items are bits
    (`_listed_blocks`), and a chosen block finds its bits by its number.
    A row whose list is not in that order answers NaN scores (which the
    templates' decode drops: nothing), never a listed item."""
    n_queries = query_vecs.shape[0]
    query_vecs, exclude = _padded_queries(query_vecs, exclude)
    maxima = _scan_maxima(query_vecs, tables, blk)
    with jax.named_scope("topk.exclude_bits"):
        block, bits, ordered = _listed_blocks(exclude, blk, maxima.shape[1])
    with jax.named_scope("topk.exclude_blocks"):
        # the padding's block is past the last: its rows are item 0's,
        # its maximum is dropped by the scatter
        scores = _rescore(query_vecs, tables, _block_item_ids(
            jnp.minimum(block, maxima.shape[1] - 1), blk))
        allowed = jnp.where(_listed_items(bits, blk), -jnp.inf, scores)
        lines = _put_maxima(
            maxima, block, allowed.reshape(*block.shape, blk).max(axis=-1))
    with jax.named_scope("topk.blocks"):
        chosen = _best_blocks(lines, k)
    with jax.named_scope("topk.rescore"):
        ids = _block_item_ids(chosen, blk)
        scores = _rescore(query_vecs, tables, ids)
    with jax.named_scope("topk.exclude"):
        # every id of a listed block carries the block's whole bits
        mine = jnp.where(chosen[:, :, None] == block[:, None, :],
                         bits[:, None, :], jnp.uint32(0)).max(axis=-1)
        scores = jnp.where(_listed_items(mine, blk), -jnp.inf, scores)
    with jax.named_scope("topk.select"):
        vals, ixs = _select_k(scores, ids, k)
        vals = jnp.where(ordered[:, None], vals, jnp.nan)
        return vals[:n_queries], ixs[:n_queries]


@functools.partial(_Counted, path_of=_counted_path)
@xray.instrument("topk.batch_topk_scores_t")
@functools.partial(jax.jit, static_argnames=("k",))
def batch_topk_scores_t(query_vecs: jax.Array,
                        table_t: jax.Array | ItemTables, k: int,
                        mask: jax.Array | None = None,
                        exclude: jax.Array | None = None,
                        allow: Allowed | None = None):
    """[B, R] x [R, M] (PRE-TRANSPOSED table) -> top-k per row:
    ``([B, k] float32 descending, [B, k] int32 item ids)``.

    The layout is the one the MXU streams: the scan reads ``[R, TM]``
    tiles of the table as the matmul's right operand with the items on
    the lanes.  ``exclude`` (``[B, E]`` int32 item ids, -1 for none)
    takes a row's listed items out of its answer.  Without a mask, over
    a long catalogue (:func:`block_items`) the call takes the blocked
    path: the table is read once, each block's best score is kept, and
    the ``k + E`` chosen blocks' items are scored again at the scan's
    precision and the excluded ones dropped; exact (:func:`_blocked_topk`).
    A list wider than 32 ids has its own blocks reduced again instead and
    k blocks chosen (:func:`_blocked_topk_listed`), exact too; on that
    path each row's ids come in :func:`listed_order`.  ``allow``
    (:class:`Allowed`: a row's category numbers beside the model's
    resident bit rows) keeps a row's answer to the items that carry one
    of its categories, tested as bits inside the scan and on the chosen
    blocks, with up to 32 excluded ids beside them.
    With a mask (additive, ``[B, M]``), a short catalogue or a large k it
    is the dense ``query_vecs @ table_t`` + ``lax.top_k``, the excluded
    ids scattered into the scores.  Serving keeps the transposed device
    copy (``DeviceTableMixin.device_item_factors_t``), so the hot path
    pays the transpose once per model advance."""
    if allow is not None:
        n_items = table_t.shape[1]
        covered = allow.rows.shape[1] * _WORD_LINES * _WORD_ITEMS
        if covered < n_items:
            raise ValueError(
                f"the category rows cover {covered} items of the table's "
                f"{n_items}: they have not followed it")
    blk = _blocked_items(query_vecs, table_t, k, mask, exclude, allow)
    if blk:   # from shapes alone  # piolint: disable=PIO104
        if exclude is not None and exclude.shape[1] > _PAIRWISE_EXCLUDE:
            return _blocked_topk_listed(query_vecs, table_t, k, blk, exclude)
        return _blocked_topk(query_vecs, table_t, k, blk, exclude, allow)
    if isinstance(table_t, ItemTables):
        table_t = table_t.packed.T if table_t.t is None else table_t.t
    with jax.named_scope("topk.scores"):
        scores = query_vecs @ table_t
        if mask is not None:
            scores = scores + mask
    if allow is not None:
        with jax.named_scope("topk.allow_bits"):
            words = _allowed_words(allow, scores.shape[0])
        with jax.named_scope("topk.allow"):
            scores = jnp.where(
                _allowed_items(words, jnp.arange(scores.shape[1],
                                                 dtype=jnp.int32)),
                scores, -jnp.inf)
    if exclude is not None:
        with jax.named_scope("topk.exclude"):
            # -1 becomes an index past the row, which the scatter drops
            rows = jnp.arange(scores.shape[0], dtype=jnp.int32)[:, None]
            scores = scores.at[
                rows, jnp.where(exclude < 0, scores.shape[1], exclude)
            ].set(-jnp.inf, mode="drop")
    with jax.named_scope("topk.select"):
        return jax.lax.top_k(scores, k)


@xray.instrument("topk.rerank_topk")
@functools.partial(jax.jit, static_argnames=("k",))
def rerank_topk(query_vecs: jax.Array, table: jax.Array,
                cand_ix: jax.Array, k: int):
    """Exact rerank stage of two-stage ANN retrieval (pio-scout):
    gather the ``[B, P]`` candidate rows from the UNQUANTIZED serving
    table and top-k them with the same full-precision dot products the
    exact scan computes — restricted to the shortlist, the scores are
    the exact scan's scores, so the candidate stage can only lose
    recall, never corrupt a kept candidate's score or rank.

    ``cand_ix`` entries of ``-1`` (IVF padding / candidate shortfall)
    score ``-inf`` and are dropped by the template decode like any
    masked row.  Returns ``([B, k] values, [B, k] int32 global ids)``.
    """
    safe = jnp.maximum(cand_ix, 0)
    rows = table[safe]                                    # [B, P, R]
    scores = jnp.einsum("bpr,br->bp", rows, query_vecs)
    scores = jnp.where(cand_ix >= 0, scores, -jnp.inf)
    vals, pos = jax.lax.top_k(scores, k)
    return vals, jnp.take_along_axis(cand_ix, pos, axis=1)


@xray.instrument("topk.cosine_topk")
@functools.partial(jax.jit, static_argnames=("k",))
def cosine_topk(query_vec: jax.Array, table: jax.Array, k: int):
    """Cosine similarity top-k (similarproduct template scoring)."""
    qn = query_vec / (jnp.linalg.norm(query_vec) + 1e-9)
    tn = table / (jnp.linalg.norm(table, axis=-1, keepdims=True) + 1e-9)
    return jax.lax.top_k(tn @ qn, k)
