"""jit'd public wrappers for the Pallas (Mosaic TPU) kernels.

These are the compiled counterparts of the sort/rank primitives the
suffix-array hot path is built from (see docs/architecture.md):

* `radix_histogram`   — per-block histograms + reduction (radix passes);
* `dense_rank_sorted` — dense ranks of lexicographically sorted rows, the
  step after every sort in the paper's Steps 1 & 3
  (`repro.core.dcv_jax` routes its sample ranking through this when
  ``sort_impl="pallas"``);
* `bitonic_stage` / `bitonic_sort` — one compare-exchange stage / a full
  row sort of the fused Lemma-1 payload.

`interpret` has no default: every caller says which mode it wants.
``interpret=True`` runs the kernel bodies through the Pallas interpreter —
the validation mode on CPU, used by `tests/kernels` and the small-n cases
of `tests/api/test_sort_impl.py`. Program paths pass
``interpret=not repro.core.compat.pallas_available()``, so on a TPU the
kernels always run compiled (Mosaic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .bitonic_stage import bitonic_sort_pallas, bitonic_stage_pallas
from .radix_hist import radix_histogram_pallas
from .seg_boundary import seg_boundary_pallas


@functools.partial(jax.jit, static_argnames=("n_bins", "block", "interpret"))
def radix_histogram(digits, n_bins: int, block: int = 1024, *,
                    interpret: bool):
    """Global histogram of `digits` (int32[N], values in [0, n_bins)).

    Pads N up to a multiple of `block` (the pad digit gets a scratch bin
    that is dropped), computes per-block MXU histograms in the kernel, and
    reduces them. Returns int32[n_bins]."""
    n = digits.shape[0]
    pad = (-n) % block
    if pad:
        digits = jnp.concatenate(
            [digits, jnp.full((pad,), n_bins, digits.dtype)])
    per_block = radix_histogram_pallas(digits, n_bins + (1 if pad else 0),
                                       block=block, interpret=interpret)
    hist = jnp.sum(per_block, axis=0)
    return hist[:n_bins]


@functools.partial(jax.jit, static_argnames=("num_keys", "block",
                                             "interpret"))
def dense_rank_sorted(rows, num_keys: int | None = None, block: int = 512,
                      *, interpret: bool):
    """Dense ranks of lexicographically sorted rows [N, W]:
    kernel computes block-local boundaries/cumsums, wrapper stitches blocks.

    Rows must already be sorted by their first `num_keys` columns (default:
    all). Equal rows share a rank; ranks are dense (0..num_distinct-1).

    Returns (ranks int32[N], num_distinct int32[])."""
    n, W = rows.shape
    num_keys = num_keys or W
    pad = (-n) % block
    if pad:
        filler = jnp.broadcast_to(rows[-1:], (pad, W))
        rows_p = jnp.concatenate([rows, filler], axis=0)
    else:
        rows_p = rows
    flags, csum, totals = seg_boundary_pallas(
        rows_p, num_keys=num_keys, block=block, interpret=interpret)
    nb = rows_p.shape[0] // block
    # stitch: true cross-block boundary = rows differ across the block edge
    edge_prev = rows_p[block - 1::block][: nb - 1] if nb > 1 else None
    base = jnp.cumsum(totals) - totals                 # exclusive block offs
    if nb > 1:
        edge_next = rows_p[block::block]
        same = jnp.ones(nb - 1, jnp.bool_)
        for c in range(num_keys):
            same = same & (edge_prev[:, c] == edge_next[:, c])
        # block b's local flag[0] forced True; if edge rows equal, every rank
        # inside block b over-counts by 1 from that false boundary.
        corr = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                jnp.cumsum(same.astype(jnp.int32))])
        base = base - corr
    ranks = (base[:, None] + csum.reshape(nb, block) - 1).reshape(-1)
    ranks = ranks[:n]
    return ranks, ranks[-1] + 1


@functools.partial(jax.jit, static_argnames=("k", "j", "num_keys", "tile",
                                             "interpret"))
def bitonic_stage(rows, k: int, j: int, num_keys: int | None = None,
                  tile: int = 256, *, interpret: bool):
    """One bitonic compare-exchange stage (k, j) over rows int32[N, W].

    N must be a power of two; rows are compared lexicographically on their
    first `num_keys` columns (default: all). Element i exchanges with i^j,
    ascending iff (i & k) == 0 — `repro.core.bitonic._stage_schedule`
    enumerates the (k, j) pairs of a full sort."""
    return bitonic_stage_pallas(rows, k, j, tile=tile, num_keys=num_keys,
                                interpret=interpret)


def bitonic_sort(rows, num_keys: int | None = None, tile: int = 256, *,
                 interpret: bool):
    """Full bitonic row sort: all (k, j) stages of `bitonic_stage` in
    sequence. rows int32[N, W] with N a power of two; sorts ascending by
    the first `num_keys` columns (append a unique index column to make the
    order total — `repro.core.dcv_jax` does exactly that for its
    ``sort_impl="pallas"`` window sort)."""
    return bitonic_sort_pallas(rows, num_keys=num_keys, tile=tile,
                               interpret=interpret)
