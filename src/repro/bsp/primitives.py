"""Shard-local vectorised primitives used by the BSP suffix-array pipeline.

Everything here runs *inside* shard_map on fixed-shape int32 arrays with
validity masks (BSP processors hold equal-size blocks; ragged reality is
expressed with masks, never dynamic shapes). Rows of W fields are held as
columns, int32[W, N]: a TPU tiles the last two dimensions by (8, 128), so
an [N, W] array with a small W pads every row to 128 lanes (a 2^23-row,
6-field payload takes 4 GB that way, 192 MB as columns).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.bitonic import vary_like


def within_group_index(group: jnp.ndarray, valid: jnp.ndarray,
                       n_groups: int):
    """For each element, its index among *valid* elements with the same
    `group` value (order = original position). Invalid elements get 0.

    Valid elements' groups lie in [0, n_groups). A one-hot prefix count
    (an [m, n_groups] cumsum) instead of a sort: callers have few groups,
    and a TPU compiles a sort instruction in minutes. Returns int32[m].
    """
    g = jnp.clip(group.astype(jnp.int32), 0, n_groups - 1)
    onehot = ((g[:, None] == jnp.arange(n_groups, dtype=jnp.int32)[None, :])
              & valid[:, None]).astype(jnp.int32)
    before = jnp.cumsum(onehot, axis=0) - onehot        # exclusive count
    out = jnp.take_along_axis(before, g[:, None], axis=1)[:, 0]
    return jnp.where(valid, out, 0)


def take_cols(rows: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """rows[:, idx] for rows held as columns [W, N], gathered one field at a
    time: a single [W, N] gather lets XLA lay the result out with W as the
    minor dimension, which a TPU pads to 128."""
    return jnp.stack([c[idx] for c in rows])


def lower_bound(a: jnp.ndarray, x: jnp.ndarray):
    """First index i with a[i] >= x (len(a) if none), for each query in x.

    a int[n] is non-decreasing. A branchless binary search: ⌈log2(n+1)⌉
    gathers in one `fori_loop` (one gather to compile, however long a is),
    and no sort instruction. Returns int32 shaped like x."""
    n = a.shape[0]
    n_steps = n.bit_length()

    def step(k, pos):
        cand = pos + jnp.left_shift(jnp.int32(1), n_steps - 1 - k)
        probe = a[jnp.minimum(cand, n) - 1]
        return jnp.where((cand <= n) & (probe < x), cand, pos)

    return jax.lax.fori_loop(0, n_steps, step,
                             vary_like(jnp.zeros(x.shape, jnp.int32), a, x))


def group_running_counts(group: jnp.ndarray, valid: jnp.ndarray,
                         n_groups: int):
    """running[g·m + i] = #{valid j ≤ i with group[j] == g}, flattened
    int32[n_groups·m], non-decreasing along its whole length.

    One 1-D cumsum over the groups laid end to end (an [n_groups, m]
    cumsum compiles several times slower for a TPU). `lower_bound` on it
    with target running[g·m − 1] + t + 1 finds the t-th member of group g.
    """
    m = group.shape[0]
    g = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), m)
    member = (jnp.tile(group.astype(jnp.int32), n_groups) == g) & jnp.tile(
        valid, n_groups)
    return jnp.cumsum(member.astype(jnp.int32))


def counts_per_bucket(dest: jnp.ndarray, valid: jnp.ndarray, p: int):
    """Histogram of dest (∈[0,p)) over valid rows → int32[p].

    One-hot matmul formulation (MXU-friendly; see kernels/radix_hist)."""
    oh = (dest[:, None] == jnp.arange(p, dtype=dest.dtype)[None, :]) & valid[:, None]
    return jnp.sum(oh.astype(jnp.int32), axis=0)


def lex_cmp_rows(a: jnp.ndarray, b: jnp.ndarray):
    """Row-wise lexicographic (a < b, a == b) for int rows given as columns
    [W, N], column 0 most significant."""
    lt = jnp.zeros(a.shape[1:], bool)
    eq = jnp.ones(a.shape[1:], bool)
    for c in range(a.shape[0]):
        lt = lt | (eq & (a[c] < b[c]))
        eq = eq & (a[c] == b[c])
    return lt, eq


def lex_lt_rows(a: jnp.ndarray, b: jnp.ndarray):
    """Row-wise lexicographic a < b for int rows as columns [W, N]; ties →
    False."""
    return lex_cmp_rows(a, b)[0]


def searchsorted_rows(splitters: jnp.ndarray, rows: jnp.ndarray, lt_fn=None):
    """dest[i] = #{s : splitter_s < row_i} for row-valued splitters.

    splitters [W, q] and rows [W, m] are columns; splitters must be sorted
    by the same order. Vectorised binary search, ⌈log2 q⌉ iterations.
    `lt_fn(a_rows, b_rows)` defaults to lexicographic on int columns.
    Returns int32[m] in [0, q].
    """
    if lt_fn is None:
        lt_fn = lex_lt_rows
    q = splitters.shape[1]
    m = rows.shape[1]
    lo = jnp.zeros(m, dtype=jnp.int32)
    hi = jnp.full(m, q, dtype=jnp.int32)
    steps = max(1, int(math.ceil(math.log2(max(q, 2)))) + 1)
    for _ in range(steps):
        mid = (lo + hi) // 2
        mid_c = jnp.clip(mid, 0, q - 1)
        s = take_cols(splitters, mid_c)
        # splitter[mid] < row  → answer is right of mid
        go_right = lt_fn(s, rows) & (mid < hi)
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, jnp.maximum(mid, lo))
    return lo
