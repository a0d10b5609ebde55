"""Batched scoring + top-k ops (the serving hot path).

Replaces the reference's predict-time cosine scan over the
``productFeatures`` RDD (`/root/reference/examples/scala-parallel-
recommendation/custom-query/src/main/scala/ALSAlgorithm.scala` predict) with
one fused XLA matmul + ``lax.top_k`` per (batch of) queries — MXU work with
a static ``k`` so the compiled executable is reused across requests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..obs import xray

__all__ = ["topk_scores", "batch_topk_scores", "batch_topk_scores_t",
           "cosine_topk", "rerank_topk", "pow2_ceil"]


def pow2_ceil(x: int) -> int:
    """Next power of two >= x (min 1).

    Serving paths round batch sizes AND k up to powers of two so the
    (B, k)-keyed XLA executables stay bounded at log2 each instead of
    compiling mid-traffic for every observed value."""
    return 1 << (max(int(x), 1) - 1).bit_length()


# xray.instrument: these three are THE serving-path executables — a
# mid-traffic recompile here (un-pow2'd k or batch) is precisely what
# the /debug/xray recompile ring exists to catch.  Their named scopes
# put `topk.scores` / `topk.select` into the HLO metadata of the product
# and of the selection, so a profile finds both by name whatever XLA
# lowers them to.
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


@xray.instrument("topk.batch_topk_scores_t")
@functools.partial(jax.jit, static_argnames=("k",))
def batch_topk_scores_t(query_vecs: jax.Array, table_t: jax.Array, k: int,
                        mask: jax.Array | None = None):
    """[B, R] x [R, M] (PRE-TRANSPOSED table) -> top-k per row.

    Identical math to :func:`batch_topk_scores`, radically different
    lowering on CPU: with the contraction dim contiguous on BOTH
    operands the batched matmul vectorizes along the M output axis —
    measured 10.6 ms -> 2.1 ms for [16, 64] x [64, 100k] f32 on one
    core (XLA's Eigen path pays a strided-RHS penalty ``@ table.T``
    that the MXU never showed).  Serving keeps a transposed device
    cache (``DeviceTableMixin.device_item_factors_t``) so the hot path
    pays the transpose once per model advance, not per batch."""
    with jax.named_scope("topk.scores"):
        scores = query_vecs @ table_t
        if mask is not None:
            scores = scores + mask
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
