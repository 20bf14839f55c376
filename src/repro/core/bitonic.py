"""Comparator-based bitonic sort in JAX.

XLA exposes only key-based sorts (`lax.sort`), but the paper's Step-4 merge
compares suffixes through the Lemma-1 offset `Λ[k_i][k_j]`, i.e. with a
*pairwise* comparator that cannot be expressed as a lexicographic key. A
bitonic network with a branchless compare-exchange is the TPU-idiomatic
answer: oblivious data movement, O(log² N) stages, every stage a vectorised
gather + select that runs at VPU rate (DESIGN.md §3.2/§3.3).

The comparator must be a *strict total order* (break ties by a unique index
column) so both elements of a pair agree on the exchange direction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _stage_schedule(n_pow2: int) -> np.ndarray:
    """All (k, j) bitonic stages for size n_pow2, as an int32[S, 2] array."""
    stages = []
    k = 2
    while k <= n_pow2:
        j = k // 2
        while j >= 1:
            stages.append((k, j))
            j //= 2
        k *= 2
    return np.asarray(stages, dtype=np.int32).reshape(-1, 2)


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def vary_like(t, *refs):
    """`t` marked varying over every manual mesh axis that `t` or any of
    `refs` varies over (a no-op outside shard_map). A loop carry that
    starts device-invariant (an iota) but is computed from varying data
    must start with that type, or `fori_loop` rejects the carry."""
    axes = frozenset().union(*(jax.typeof(r).vma for r in refs))
    axes = axes - jax.typeof(t).vma
    return jax.lax.pcast(t, tuple(sorted(axes)), to="varying") if axes else t


def bitonic_sort(payload: dict, lt_fn, *, unroll: bool = False) -> dict:
    """Sort `payload` (dict of arrays sharing leading dim N, a power of two)
    ascending by the strict total order `lt_fn(a, b) -> bool[N]`.

    `lt_fn` receives two payload dicts (self, partner) and must return
    element-wise "self strictly precedes partner". Ties must be impossible
    (give every element a unique tiebreak column).
    """
    leaves = jax.tree_util.tree_leaves(payload)
    n = leaves[0].shape[0]
    assert n & (n - 1) == 0, f"bitonic_sort needs power-of-two length, got {n}"
    if n <= 1:
        return payload
    # every stage mixes all leaves: give each the varying axes of all
    payload = jax.tree_util.tree_map(lambda t: vary_like(t, *leaves), payload)
    schedule = jnp.asarray(_stage_schedule(n))
    idx = jnp.arange(n, dtype=jnp.int32)

    def one_stage(payload, kj):
        k, j = kj[0], kj[1]
        partner = idx ^ j
        up = (idx & k) == 0
        other = jax.tree_util.tree_map(lambda t: t[partner], payload)
        lt = lt_fn(payload, other)
        lower = idx < partner
        # pair (low, high): low ends up with min iff ascending. Element keeps
        # its own value iff  (lt(self,partner) == lower) == up.
        keep = ((lt == lower) == up)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(
                keep.reshape((-1,) + (1,) * (a.ndim - 1)), a, b
            ),
            payload, other,
        )

    if unroll:
        for s in np.asarray(schedule):
            payload = one_stage(payload, jnp.asarray(s))
        return payload

    def body(s, payload):
        return one_stage(payload, schedule[s])

    return jax.lax.fori_loop(0, schedule.shape[0], body, payload)


def lex_lt_int(a_cols: jnp.ndarray, b_cols: jnp.ndarray):
    """Vectorised lexicographic (lt, all_eq) over trailing axis of int cols.

    a_cols, b_cols: int[N, W]. Returns (lt: bool[N], eq: bool[N]) without
    unrolling over W (argmax-of-first-difference trick).
    """
    neq = a_cols != b_cols
    any_neq = jnp.any(neq, axis=-1)
    first = jnp.argmax(neq, axis=-1)  # 0 when all equal (masked by any_neq)
    a_star = jnp.take_along_axis(a_cols, first[:, None], axis=-1)[:, 0]
    b_star = jnp.take_along_axis(b_cols, first[:, None], axis=-1)[:, 0]
    lt = jnp.where(any_neq, a_star < b_star, False)
    return lt, ~any_neq


@functools.partial(jax.jit, static_argnames=("num_cols",))
def sort_rows_with_index(cols: jnp.ndarray, num_cols: int):
    """Key-based row sort via variadic lax.sort: returns permutation.

    cols: int32[N, W] with W == num_cols. Final tiebreak = row index, making
    the sort stable and the permutation unique.
    """
    n = cols.shape[0]
    operands = tuple(cols[:, c] for c in range(num_cols)) + (
        jnp.arange(n, dtype=jnp.int32),
    )
    out = jax.lax.sort(operands, num_keys=num_cols + 1)
    return out[-1]
