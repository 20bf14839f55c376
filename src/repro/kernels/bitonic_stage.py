"""Pallas TPU kernel: one bitonic compare-exchange stage over multi-column
payload rows (the inner step of the paper's fused Lemma-1 comparator sort,
DESIGN §3.3).

Stage (k, j): element i exchanges with i^j, ascending iff (i & k) == 0.
The kernels see the rows transposed, `[W, N]`: rows run along lanes, so a
tile is `[W, tile]` (lane-dense; `tile` a multiple of 128 on the chip) and
an in-tile partner is a lane rotation away. `(k, j)` reach the kernel as
scalar-prefetch operands, not compile-time constants, so a whole sort
compiles two programs per shape instead of one per stage. Two tiling
regimes:
  * j >= tile: partners live in different tiles → the grid walks *pairs* of
    tiles (low tile t, high tile t + j/T); two input refs per program, whose
    index maps read j from the prefetched scalars.
  * j <  tile: partners are inside one tile → single-ref program, partner
    by lane rotation.
The comparator is lexicographic over the first `num_keys` columns (unrolled
at trace time — the payload width is a compile-time constant).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.bitonic import _stage_schedule


def _lex_lt(a, b, num_keys: int):
    """Strict lexicographic a < b over [W, T] tiles (columns along rows of
    the tile, unrolled) → bool[1, T]."""
    lt = jnp.zeros((1,) + a.shape[1:], jnp.bool_)
    eq = jnp.ones((1,) + a.shape[1:], jnp.bool_)
    for c in range(num_keys):
        ac, bc = a[c:c + 1], b[c:c + 1]
        lt = lt | (eq & (ac < bc))
        eq = eq & (ac == bc)
    return lt


def _low_tile(p, j_t):
    """Grid step p → index of the low tile of its (low, low + j_t) pair."""
    return (p // j_t) * (2 * j_t) + p % j_t


def _cross_tile_kernel(kj_ref, low_ref, high_ref, low_out, high_out, *,
                       tile: int, num_keys: int):
    k = kj_ref[0]
    base = _low_tile(pl.program_id(0), kj_ref[1] // tile) * tile
    idx = base + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    up = (idx & k) == 0
    a = low_ref[...]
    b = high_ref[...]
    keep = _lex_lt(a, b, num_keys) == up
    low_out[...] = jnp.where(keep, a, b)
    high_out[...] = jnp.where(keep, b, a)


def _in_tile_kernel(kj_ref, x_ref, out_ref, *, tile: int, num_keys: int):
    k, j = kj_ref[0], kj_ref[1]
    x = x_ref[...]                                       # [W, tile]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    idx = pl.program_id(0) * tile + lane
    # partner lane ^ j is j lanes away; of the two rotations by j, take the
    # one that lands it (tested on the rotated iota, so independent of the
    # rotation's direction)
    other = jnp.where(pltpu.roll(lane, j, 1) == (lane ^ j),
                      pltpu.roll(x, j, 1), pltpu.roll(x, tile - j, 1))
    up = (idx & k) == 0
    lower = (idx & j) == 0
    keep = (_lex_lt(x, other, num_keys) == lower) == up
    out_ref[...] = jnp.where(keep, x, other)


@functools.partial(jax.jit, static_argnames=("cross", "tile", "num_keys",
                                             "interpret"))
def _stage_t(xt, kj, *, cross: bool, tile: int, num_keys: int,
             interpret: bool):
    """One stage on transposed rows xt int32[W, N]; kj = int32[2] (k, j)."""
    W, n = xt.shape
    if cross:
        def low_map(p, kj_ref):
            return (0, _low_tile(p, kj_ref[1] // tile))

        def high_map(p, kj_ref):
            j_t = kj_ref[1] // tile
            return (0, _low_tile(p, j_t) + j_t)

        spec_lo = pl.BlockSpec((W, tile), low_map)
        spec_hi = pl.BlockSpec((W, tile), high_map)
        low, high = pl.pallas_call(
            functools.partial(_cross_tile_kernel, tile=tile,
                              num_keys=num_keys),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(n // tile // 2,),
                in_specs=[spec_lo, spec_hi], out_specs=[spec_lo, spec_hi]),
            out_shape=[jax.ShapeDtypeStruct((W, n), jnp.int32)] * 2,
            interpret=interpret,
        )(kj, xt, xt)
        # low/high outputs each hold their half; merge by tile parity
        j_t = kj[1] // tile
        is_low = (jnp.arange(n, dtype=jnp.int32) // tile) % (2 * j_t) < j_t
        return jnp.where(is_low[None, :], low, high)

    spec = pl.BlockSpec((W, tile), lambda p, kj_ref: (0, p))
    return pl.pallas_call(
        functools.partial(_in_tile_kernel, tile=tile, num_keys=num_keys),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tile,),
            in_specs=[spec], out_specs=spec),
        out_shape=jax.ShapeDtypeStruct((W, n), jnp.int32),
        interpret=interpret,
    )(kj, xt)


def _stages_t(xt, stages, *, tile: int, num_keys: int, interpret: bool):
    """Apply (k, j) stages in order to transposed rows xt int32[W, N]."""
    n = xt.shape[1]
    tile = min(tile, n)
    for k, j in stages:
        xt = _stage_t(xt, jnp.asarray([k, j], jnp.int32), cross=j >= tile,
                      tile=tile, num_keys=num_keys, interpret=interpret)
    return xt


def bitonic_stage_pallas(rows: jnp.ndarray, k: int, j: int, *,
                         tile: int = 256, num_keys: int | None = None,
                         interpret: bool) -> jnp.ndarray:
    """Apply one (k, j) compare-exchange stage. rows int32[N, W], N pow2."""
    n, W = rows.shape
    assert n & (n - 1) == 0, n
    return _stages_t(rows.T, [(k, j)], tile=tile, num_keys=num_keys or W,
                     interpret=interpret).T


def bitonic_sort_pallas(rows: jnp.ndarray, *, num_keys: int | None = None,
                        tile: int = 256, interpret: bool):
    """Full sort via repeated stages (tests/bench; the production sort fuses
    stages in repro.core.bitonic — this kernel is the per-stage hot loop).
    Transposes once, runs every stage on the `[W, N]` layout."""
    n, W = rows.shape
    assert n & (n - 1) == 0, n
    if n <= 1:
        return rows
    return _stages_t(rows.T, _stage_schedule(n).tolist(), tile=tile,
                     num_keys=num_keys or W, interpret=interpret).T
