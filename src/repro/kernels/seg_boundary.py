"""Pallas TPU kernel: segment boundaries + in-block prefix sums over sorted
rows — the dense-ranking step after every sort (paper steps 1 & 3).

Per block of T rows: flag[i] = any(row[i] != row[i-1]) (block-local; the
wrapper stitches the T-boundaries), plus the block-inclusive cumsum of flags
and the block total, so the wrapper finishes global dense ranks with one tiny
exclusive scan over block totals.

The kernel sees the rows transposed, `[W, T]`: rows run along lanes, so the
previous row is one lane rotation away and the in-block cumsum is one MXU
matmul of the flag row with an upper-triangular ones matrix. Outputs are
`[n_blocks, 1, T]` (flags, cumsum) and `[n_blocks, 1, 128]` (total,
broadcast along the lanes), which keeps every block within the (8, 128)
tiling rule for any T that is a multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _seg_kernel(rows_ref, flags_ref, csum_ref, total_ref, *, num_keys: int):
    x = rows_ref[...]                                    # [W, T]
    t = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
    # previous row: whichever lane rotation brings lane i-1 to lane i (the
    # test on the rotated iota keeps this independent of roll's direction)
    prev = jnp.where(pltpu.roll(lane, 1, 1) == lane - 1,
                     pltpu.roll(x, 1, 1), pltpu.roll(x, t - 1, 1))
    # block-local convention: the first row of every block is a boundary;
    # the wrapper stitches true cross-block boundaries.
    neq = lane == 0
    for c in range(num_keys):
        neq = neq | (x[c:c + 1] != prev[c:c + 1])
    flags = neq.astype(jnp.int32)                        # [1, T]
    upper = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))
    cs = jnp.dot(neq.astype(jnp.bfloat16), upper.astype(jnp.bfloat16),
                 preferred_element_type=jnp.float32).astype(jnp.int32)
    flags_ref[...] = flags
    csum_ref[...] = cs
    total_ref[...] = jnp.broadcast_to(cs[:, t - 1:], (1, _LANES))


def seg_boundary_pallas(rows: jnp.ndarray, num_keys: int | None = None,
                        block: int = 512, *, interpret: bool):
    """rows int32[N, W] sorted → (flags int32[N], csum int32[N],
    totals int32[N//block]). N multiple of block."""
    n, W = rows.shape
    assert n % block == 0
    num_keys = num_keys or W
    nb = n // block
    flags, csum, totals = pl.pallas_call(
        functools.partial(_seg_kernel, num_keys=num_keys),
        grid=(nb,),
        in_specs=[pl.BlockSpec((W, block), lambda p: (0, p))],
        out_specs=[pl.BlockSpec((None, 1, block), lambda p: (p, 0, 0)),
                   pl.BlockSpec((None, 1, block), lambda p: (p, 0, 0)),
                   pl.BlockSpec((None, 1, _LANES), lambda p: (p, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, block), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1, block), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1, _LANES), jnp.int32)],
        interpret=interpret,
    )(rows.T)
    return flags.reshape(n), csum.reshape(n), totals[:, 0, 0]
