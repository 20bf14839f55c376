"""Device argsort of key lanes, built from single-operand sorts.

XLA's TPU compiler takes minutes to compile a multi-operand `lax.sort` at
the sizes one suffix-array level sorts, and the time grows with every
operand: compiled for a described v5e, four int32 operands of 2^16 rows
took 207 s and two operands of 2^25 rows 125 s, while ONE operand of 2^25
rows took 55 s. `lsd_argsort` therefore orders rows given as unsigned key
lanes by LSD radix passes over digits: a pass packs one digit of one lane
above each row's current position (`digit << idx_bits | position`), sorts
that single uint32 operand, and reads the new order off the low bits.
Positions are unique, so every pass is stable and ties keep ascending row
order. The passes run in one `fori_loop`, so a whole argsort holds one
sort instruction — one compile per (lanes, rows) shape — however many
passes its key widths need.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from .bitonic import vary_like

#: one event per jax trace of `lsd_argsort` (tests assert cache reuse).
TRACE_COUNTS: collections.Counter = collections.Counter()


def _idx_bits(n: int) -> int:
    return max(1, (n - 1).bit_length())


def radix_schedule(bits, n: int) -> np.ndarray:
    """LSD passes for lanes of `bits` significant bits over n rows:
    int32[P, 3] of (lane, shift, width), least significant digit first."""
    digit = 32 - _idx_bits(n)
    if digit < 1:
        raise ValueError(f"lsd_argsort needs n < 2^31 rows, got {n}")
    passes = [(lane, shift, min(digit, b - shift))
              for lane, b in reversed(list(enumerate(bits)))
              for shift in range(0, b, digit)]
    return np.asarray(passes, np.int32).reshape(-1, 3)


@jax.jit
def _lsd_argsort(lanes, sched, n_passes):
    # saca-lint: allow[TRACE001] deliberate: trace-time retrace counter, mutated only while tracing, read by tests via total_traces()
    TRACE_COUNTS["lsd_argsort"] += 1
    n = lanes.shape[1]
    idx_bits = _idx_bits(n)
    pos = jnp.arange(n, dtype=jnp.uint32)
    low = jnp.uint32((1 << idx_bits) - 1)

    def one_pass(i, perm):
        lane, shift, width = sched[i, 0], sched[i, 1], sched[i, 2]
        mask = (jnp.uint32(1) << width.astype(jnp.uint32)) - 1
        digit = (lanes[lane][perm] >> shift.astype(jnp.uint32)) & mask
        key = jax.lax.sort((digit << idx_bits) | pos)    # ONE operand
        return perm[(key & low).astype(jnp.int32)]

    return jax.lax.fori_loop(
        0, n_passes, one_pass,
        vary_like(jnp.arange(n, dtype=jnp.int32), lanes))


def lsd_argsort(lanes, bits) -> jnp.ndarray:
    """Stable lexicographic argsort of rows given as key lanes.

    lanes: uint32[L, n], lane 0 most significant; lane i holds values below
    2**bits[i]. Returns int32[n], ties in ascending row order. The pass
    schedule is data, padded to the most passes any widths can need, so
    the compiled program depends on (L, n) only."""
    lanes = jnp.asarray(lanes, jnp.uint32)
    L, n = lanes.shape
    sched = radix_schedule(bits, n)
    full = np.zeros((len(radix_schedule([32] * L, n)), 3), np.int32)
    full[:len(sched)] = sched
    return _lsd_argsort(lanes, jnp.asarray(full), jnp.int32(len(sched)))
