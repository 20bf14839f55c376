"""Deterministic ragged row exchange over dense all_to_all (two hops).

XLA:CPU cannot lower `ragged-all-to-all` (and real-TPU deployments may prefer
static shapes anyway), so we emulate the paper's "send each element to its
bucket's processor" h-relation with two dense all_to_all hops and
*per-destination round-robin* intermediate placement:

  hop 1: row r — the i-th valid row of this shard destined to shard d — is
         sent to intermediate shard q = i mod p. Per-(src,q) traffic is
         ≤ Σ_d ⌈n_{s,d}/p⌉ ≤ m/p + p rows: cap1 = ⌈m/p⌉ + p.
  hop 2: intermediate q forwards to d; per-(q,d) traffic is
         Σ_s ⌈n_{s,d}/p⌉ ≤ total_d/p + p ≤ cap_out/p + p rows.

Both caps are *deterministic* (adversarial-input safe), so total per-shard
communication is O(m + p²) words per exchange — the paper's O(n/p) given the
slackness n ≥ p³ (§5, Algorithm 2). Exactly 2 supersteps: the overflow flag
is computed *locally* (no extra collective), so the superstep count per
exchange really is 2 and `BSPCounters` accounting matches execution.

Every step builds its buffers by gathers (a binary search over a running
count per group), never by a scatter — XLA lowers an unsorted scatter on a
TPU through a sort — and the receiver restores each source's order from
the per-(intermediate, source) counts it receives. Rows travel as columns
int32[W, m] (see `repro.bsp.primitives`).

Overflow contract
-----------------
`exchange` returns a shard-local `overflowed` flag covering every way a cap
can be exceeded (hop-1 slots, hop-2 slots, cap_out arrivals). The flag is a
**bug detector, not a runtime condition**: every call site's cap is sound
by construction, so a set flag means the caller's bound is wrong. All call
sites gather the flag across shards (out_specs P(axis)) and raise
RuntimeError — see `repro.bsp.suffix_array._check_overflow` and
`repro.bsp.psort.run_psort`. The audit of the four call sites:

  psort bucket exchange   cap_out = 2m + 2p + 4  (regular-sampling bound:
                          p(p+1) samples ⇒ every bucket < 2·m_tot/p + slack)
  psort rebalance         cap_out = m            (shard d receives exactly
                          the rows with gpos ∈ [d·m, (d+1)·m))
  SM1 rank routing        cap_out = m_loc        (block-major index j is a
                          bijection onto [0, p·m_loc))
  SM2 rank un-routing     cap_out = m_loc        (each shard owns exactly
                          m_loc sample positions)

`impl="ragged"` plugs in jax.lax.ragged_all_to_all on backends that support
it (TPU); semantics and caps are identical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .primitives import group_running_counts, lower_bound, take_cols


def hop_caps(m: int, p: int, cap_out: int) -> tuple[int, int]:
    cap1 = -(-m // p) + p
    cap2 = -(-cap_out // p) + p
    return cap1, cap2


def _fill_slots(cols, group, valid, n_groups: int, cap: int):
    """buf[:, g, t] = the t-th valid row of `group` g (row order), -1 past
    the group's end: int32[W, n_groups, cap]; plus the group sizes.

    A gather, not a scatter: each slot finds its row by a binary search
    over the groups' running counts. (XLA lowers an unsorted scatter on a
    TPU through a sort, whose compile takes minutes at these sizes.)"""
    m = group.shape[0]
    running = group_running_counts(group, valid, n_groups)
    ends = running[m - 1::m]                                   # [n_groups]
    sizes = ends - jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
    t = jnp.arange(cap, dtype=jnp.int32)
    g = jnp.arange(n_groups, dtype=jnp.int32)[:, None]
    at = lower_bound(running, ((ends - sizes)[:, None] + t + 1).reshape(-1))
    src = jnp.clip(at - (g * m).reshape(-1).repeat(cap), 0, m - 1)
    filled = (t < sizes[:, None]).reshape(-1)
    buf = jnp.where(filled[None], take_cols(cols, src), -1)
    return buf.reshape(cols.shape[0], n_groups, cap), sizes


def exchange(
    cols: jnp.ndarray,       # int32[W, m] (local rows as columns)
    dest: jnp.ndarray,       # int32[m] ∈ [0, p)
    valid: jnp.ndarray,      # bool[m]
    *,
    p: int,
    cap_out: int,
    axis: str,
):
    """Route valid rows to their dest shards, preserving order.

    Returns (out int32[W, cap_out], out_valid bool[cap_out],
    overflowed bool[]). A shard receives its rows as a prefix, grouped by
    source shard in shard order, each source's rows in their original
    order — so rows that were globally sorted and are sent to contiguous
    ranges arrive sorted. `overflowed` is this shard's local OR that any
    capacity was exceeded; callers MUST return it through out_specs
    P(axis) and raise on `any()` (see the module docstring — a set flag is
    a caller bug, never a recoverable condition).
    """
    W, m = cols.shape
    cap1, cap2 = hop_caps(m, p, cap_out)
    me = jax.lax.axis_index(axis).astype(jnp.int32)
    dest = dest.astype(jnp.int32)

    # ---- hop 1: the i-th row bound for d goes to intermediate i mod p ----
    running = group_running_counts(dest, valid, p)
    i_d = running[jnp.clip(dest, 0, p - 1) * m
                  + jnp.arange(m, dtype=jnp.int32)] - 1
    i_d = i_d - jnp.concatenate([jnp.zeros(1, jnp.int32),
                                 running[m - 1:-1:m]])[jnp.clip(dest, 0,
                                                                p - 1)]
    tag = jnp.where(valid, me * p + dest, -1)          # (source, dest)
    buf1, sizes1 = _fill_slots(jnp.concatenate([tag[None], cols]),
                               i_d % p, valid, p, cap1)
    recv1 = jax.lax.all_to_all(buf1, axis, split_axis=1, concat_axis=1,
                               tiled=False)            # [W+1, source, cap1]
    flat1 = recv1.reshape(W + 1, p * cap1)

    # ---- hop 2: forward to the true destination ----
    valid2 = flat1[0] >= 0
    buf2, sizes2 = _fill_slots(flat1, flat1[0] % p, valid2, p, cap2)
    recv2 = jax.lax.all_to_all(buf2, axis, split_axis=1, concat_axis=1,
                               tiled=False)            # [W+1, inter, cap2]
    flat2 = recv2.reshape(W + 1, p * cap2)

    # ---- restore (source, original order) ----
    # Block q holds, source by source, the rows whose index i among the
    # source's rows for this shard has i mod p == q, in order; so row i of
    # source s sits at q·cap2 + (rows of sources < s in block q) + i // p.
    src = jnp.where(flat2[0] >= 0, flat2[0] // p, p).reshape(p, cap2)
    per = jnp.stack([jnp.sum((src == s).astype(jnp.int32), axis=1)
                     for s in range(p)], axis=1)                 # [q, s]
    start_in_block = (jnp.cumsum(per, axis=1) - per).reshape(-1)
    from_src = jnp.sum(per, axis=0)                              # [s]
    first = jnp.cumsum(from_src) - from_src
    n_got = jnp.sum(from_src)
    u = jnp.arange(cap_out, dtype=jnp.int32)
    s_of = sum((first[s] <= u).astype(jnp.int32) for s in range(1, p))
    i = u - first[s_of]
    q = i % p
    at = q * cap2 + start_in_block[q * p + s_of] + i // p
    out_valid = u < n_got
    out = jnp.where(out_valid[None, :],
                    take_cols(flat2[1:], jnp.where(out_valid, at, 0)), -1)

    overflowed = (jnp.any(sizes1 > cap1) | jnp.any(sizes2 > cap2)
                  | (n_got > cap_out))
    return out, out_valid, overflowed
