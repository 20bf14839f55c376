"""Pallas TPU kernel: per-block radix histogram via one-hot matmul.

Counting sort's scatter-increment is TPU-hostile (serialised scatter units).
The TPU-native reformulation: one-hot-encode the digit block and reduce with
a matmul — the reduction runs on the MXU at full rate (DESIGN §3.2). This is
the inner loop of every counting/radix sort in the paper (steps 1–3).

Grid: one program per block of `block` digits. Digits and histograms are
laid out as `[n_blocks, 1, width]` so every block is one lane-major row —
the (8, 128) tiling rule holds for any `block` that is a multiple of 128
and any `n_bins` (a block's last two dims equal the array's).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hist_kernel(digits_ref, out_ref, *, n_bins: int):
    d = digits_ref[...]                                  # [1, block] int32
    block = d.shape[1]
    bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, block), 0)
    onehot = (d == bins).astype(jnp.bfloat16)            # [n_bins, block]
    ones = jnp.ones((1, block), jnp.bfloat16)
    # MXU reduction: [1, block] · [n_bins, block]ᵀ → [1, n_bins]; 0/1
    # operands are exact in bf16 and the f32 accumulator holds ≤ block.
    hist = jax.lax.dot_general(ones, onehot, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    out_ref[...] = hist.astype(jnp.int32)


def radix_histogram_pallas(digits: jnp.ndarray, n_bins: int,
                           block: int = 1024, *, interpret: bool):
    """digits int32[N] (N multiple of block) → int32[N//block, n_bins]."""
    n = digits.shape[0]
    assert n % block == 0, (n, block)
    nb = n // block
    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins),
        grid=(nb,),
        in_specs=[pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, n_bins), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, n_bins), jnp.int32),
        interpret=interpret,
    )(digits.reshape(nb, 1, block))
    return out.reshape(nb, n_bins)
