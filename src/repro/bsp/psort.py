"""Algorithm 2 — parallel sorting by regular sampling (Shi–Schaeffer /
Chan–Dehne), generic over key-based and comparator-based orders, with
pluggable **shard-local sorts** (`SAOptions.sort_impl`).

Row contract
------------
Rows are held as columns, int32[W, m_local] (see `repro.bsp.primitives`
for why), with a fixed field layout:
  field 0      : valid flag (0 = valid, 1 = pad)  — pads sort last,
  field 1..W-2 : payload (keys first for key-mode),
  field W-1    : unique global index — strict total-order tiebreak.
`lt_fn(a, b) -> bool[N]` must be a strict total order consistent with that
contract; `local_sort(rows) -> rows` must sort by the same order.

Local-sort implementations
--------------------------
==========  ===============================================================
"radix"     packed keys: the key columns are packed into as few 30-bit
            int32 lanes as their value range allows (`pack_key_columns` —
            order-preserving and injective, so lexicographic order and
            row equality are unchanged), then ONE key sort
            (`repro.core.lsd_sort.lsd_argsort`) orders everything; a
            Lemma-1 comparator tail (when configured,
            `make_local_sort_keyed`) runs as a *cond-gated* bitonic pass
            that only fires when the key sort left equal-key runs.
"lax"       the same two-phase sort over the raw (unpacked) key columns.
"bitonic"   the legacy comparator network over full payload rows
            (`make_local_sort_bitonic`) — O(m log² m) compare-exchanges
            with the Lemma-1 comparator at every stage. Kept as the
            executable reference and the `benchmarks/bsp_throughput.py`
            regression row.
==========  ===============================================================

Supersteps per call: 6 (sample gather, 2×a2a bucket exchange, count gather,
2×a2a rebalance) — O(1) as in the paper. Communication per shard:
O(m_local + p²) words (regular-sampling bucket bound 2m/p + slack); the
packed-key layout shrinks every exchanged row from ~v to ⌈v·bits/30⌉ key
lanes, so the same h-relation moves proportionally fewer words.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np

from ..core.bitonic import bitonic_sort, next_pow2
from ..core.lsd_sort import lsd_argsort
from .exchange import exchange
from .primitives import (lex_cmp_rows, lex_lt_rows, searchsorted_rows,
                         take_cols)

INT32_MAX = jnp.iinfo(jnp.int32).max

#: accepted BSP `sort_impl` values ("auto" resolves via
#: `resolve_bsp_sort_impl`; "pallas" is jax-backend-only and rejected).
BSP_SORT_IMPLS = ("auto", "radix", "lax", "bitonic")


def resolve_bsp_sort_impl(sort_impl: str, pack_keys: bool = True) -> str:
    """Concrete shard-local sort implementation for the BSP backend.

    ``"auto"`` resolves to the packed-key path (``"radix"``) unless key
    packing is disabled (`pack_keys=False`), in which case the unpacked
    multi-key sort (``"lax"``) is used. ``"pallas"`` (valid for the jax
    backend) has no BSP lowering — shard-local sorts run inside shard_map
    where the Mosaic kernels cannot be dispatched per shard — and is
    rejected with an explicit error rather than silently remapped.
    """
    if sort_impl == "auto":
        return "radix" if pack_keys else "lax"
    if sort_impl not in BSP_SORT_IMPLS:
        raise ValueError(
            f"sort_impl {sort_impl!r} is not supported by the bsp backend; "
            f"expected one of {BSP_SORT_IMPLS}")
    return sort_impl


# --------------------------------------------------------------------------
# key packing (§Perf SA-iteration A; Rajasekaran & Nicolae's radix-on-
# packed-keys trick applied to the BSP row layout)
# --------------------------------------------------------------------------
def pack_key_columns(cols: jnp.ndarray, lo: int, hi: int) -> jnp.ndarray:
    """Pack integer key columns with a known value range into 30-bit lanes.

    cols int[k, m] with every value in [lo, hi] → int32[⌈k/per⌉, m] where
    `per = ⌊30 / bits⌋` fixed-width fields of `bits = bit_length(hi - lo)`
    are packed big-endian into each lane. Fixed-width fields make the
    packing *order-preserving* (lexicographic comparison of the packed
    lanes equals lexicographic comparison of the original columns) and
    *injective* (row equality is preserved exactly). Returns `cols`
    unchanged when a field does not fit at least twice into 30 bits —
    packing would not reduce the width. 30 bits (not 31) keeps every
    packed lane strictly below INT32_MAX, so pad rows still sort last.
    """
    k = cols.shape[0]
    span = max(1, int(hi) - int(lo))
    bits = span.bit_length()
    per = max(1, 30 // bits)
    if per < 2:
        return cols
    shifted = (cols - lo).astype(jnp.int32)
    lanes = []
    for c in range(0, k, per):
        lane = jnp.zeros(cols.shape[1:], jnp.int32)
        for j in range(per):
            field = shifted[c + j] if c + j < k else 0
            lane = lane + field * (1 << (bits * (per - 1 - j)))
        lanes.append(lane)
    return jnp.stack(lanes)


def packed_width(k: int, lo: int, hi: int) -> int:
    """Number of int32 key lanes `pack_key_columns` produces for k columns."""
    span = max(1, int(hi) - int(lo))
    per = max(1, 30 // span.bit_length())
    return k if per < 2 else -(-k // per)


def quantize_sigma(sigma: int) -> int:
    """Round an alphabet bound up to the largest bound with the same packed
    field width (`bit_length(sigma + 1)` bits for values in [-1, sigma]).

    The packed key layout — and therefore every traced shape downstream —
    depends on sigma only through that bit width, but sigma itself is
    data-dependent (max(x) + 1 per recursion level) and is a *static* jit
    argument of the SM stages. Quantising collapses the open-ended family
    of observed maxima onto O(log σ) distinct static values, so nearby
    inputs (max 200 vs 201) reuse compiled programs instead of retracing.
    Always ≥ sigma, so the value range stays sound."""
    return (1 << (int(sigma) + 1).bit_length()) - 2


# --------------------------------------------------------------------------
# pad rows + orders
# --------------------------------------------------------------------------
def make_pad_rows(k: int, W: int, tag_base: int = 1 << 29):
    """k pad rows as columns [W, k]: valid=1, payload=MAX, unique huge
    tiebreak index."""
    return jnp.concatenate([
        jnp.ones((1, k), jnp.int32),
        jnp.full((W - 2, k), INT32_MAX, jnp.int32),
        (tag_base + jnp.arange(k, dtype=jnp.int32))[None]])


def lex_lt_full(a: jnp.ndarray, b: jnp.ndarray):
    """Default strict total order: lexicographic over ALL fields.

    Strict because field W-1 is unique."""
    return lex_lt_rows(a, b)


def argsort_columns(cols: jnp.ndarray) -> jnp.ndarray:
    """Stable lexicographic argsort of int32 rows given as columns [k, m]
    (column 0 most significant) by `lsd_argsort`: flipping the sign bit
    maps int32 order onto uint32 order."""
    lanes = jax.lax.bitcast_convert_type(cols, jnp.uint32) ^ jnp.uint32(
        1 << 31)
    return lsd_argsort(lanes, [32] * cols.shape[0])


def local_sort_lex(rows: jnp.ndarray) -> jnp.ndarray:
    return take_cols(rows, argsort_columns(rows))


def sort_few(rows: jnp.ndarray, lt_fn) -> jnp.ndarray:
    """Stable sort of a handful of rows [W, k] by `lt_fn` through all k²
    pairwise comparisons: no sort instruction, which a TPU compiles in tens
    of seconds even for a few rows. For the p(p+1) regular samples."""
    k = rows.shape[1]
    a = jnp.repeat(rows, k, axis=1)                 # a[:, i·k + j] = row i
    b = jnp.tile(rows, (1, k))                      # b[:, i·k + j] = row j
    j_lt_i = lt_fn(b, a).reshape(k, k)
    i_lt_j = lt_fn(a, b).reshape(k, k)
    idx = jnp.arange(k, dtype=jnp.int32)
    before = j_lt_i | (~i_lt_j & (idx[None, :] < idx[:, None]))
    rank = jnp.sum(before.astype(jnp.int32), axis=1)    # a permutation
    return take_cols(rows, jnp.argmax(rank[None, :] == idx[:, None], axis=1))


def _bitonic_rows(rows: jnp.ndarray, lt_fn) -> jnp.ndarray:
    """`bitonic_sort` of rows [W, N] (N a power of two) by `lt_fn`."""
    out = bitonic_sort({"cols": tuple(rows)},
                       lambda a, b: lt_fn(jnp.stack(a["cols"]),
                                          jnp.stack(b["cols"])))
    return jnp.stack(out["cols"])


def make_local_sort_bitonic(lt_fn):
    def local_sort(rows: jnp.ndarray) -> jnp.ndarray:
        W, m = rows.shape
        n2 = next_pow2(m)
        if n2 != m:
            rows = jnp.concatenate([rows, make_pad_rows(n2 - m, W)], axis=1)
        return _bitonic_rows(rows, lt_fn)[:, :m]
    return local_sort


# --------------------------------------------------------------------------
# Lemma-1 payload order over packed/unpacked keys
# --------------------------------------------------------------------------
def _pick(fields, idx):
    """fields[idx[i]][i] for a short list of equal-shape fields."""
    out = fields[0]
    for k in range(1, len(fields)):
        out = jnp.where(idx == k, fields[k], out)
    return out


def make_payload_lt(nk: int, v: int, dsize: int, lam_i1, lam_i2):
    """Strict total order on Lemma-1 payload rows
    [valid | keys(nk) | ranks(|D|) | klass | gidx] (as columns).

    The head (valid flag + nk key lanes — packed or raw characters) is
    compared lexicographically; head-equal rows (identical v-character
    windows) are resolved by the paper's Lemma-1 rank lookup
    `rank[i + Λ[k_i][k_j]]` via the per-class index tables, then by the
    unique gidx field. `v` bounds the klass clip (pads carry INT32_MAX)."""
    cr = 1 + nk
    ck = 1 + nk + dsize
    cg = 2 + nk + dsize

    def lt(a, b):
        ka = jnp.clip(a[ck], 0, v - 1)
        kb = jnp.clip(b[ck], 0, v - 1)
        lt_head, eq_head = lex_cmp_rows(a[: 1 + nk], b[: 1 + nk])
        ra = _pick(a[cr:ck], lam_i1[ka, kb])
        rb = _pick(b[cr:ck], lam_i2[ka, kb])
        return jnp.where(
            eq_head & (ra != rb), ra < rb,
            jnp.where(eq_head, a[cg] < b[cg], lt_head))

    return lt


def make_local_sort_keyed(nk: int, v: int, dsize: int, lam_i1, lam_i2):
    """Two-phase shard-local sort by the `make_payload_lt` order.

    Phase 1 is ONE key sort over (valid | keys | gidx) (`argsort_columns`)
    — the packed-key fast path that replaces the comparator-bitonic network
    for the bulk O(m log m) work. Phase 2 resolves *equal-key runs* (suffix
    pairs sharing their full v-character window — the only pairs Lemma 1
    is needed for) with a bitonic pass whose comparator is (run id,
    Λ-rank, slot); the pass is wrapped in `lax.cond` and skipped entirely
    when the key sort left no ties among valid rows, which is the common
    case for realistic alphabets. Pad rows never trigger the pass: their
    relative order is already fixed by the unique gidx sort key.
    """
    cr = 1 + nk
    ck = 1 + nk + dsize
    cg = 2 + nk + dsize

    def local_sort(rows: jnp.ndarray) -> jnp.ndarray:
        W, m = rows.shape
        rows = take_cols(rows, argsort_columns(jnp.concatenate(
            [rows[: 1 + nk], rows[cg:cg + 1]])))
        head = rows[: 1 + nk]
        boundary = jnp.concatenate([
            jnp.ones(1, bool), jnp.any(head[:, 1:] != head[:, :-1], axis=0)])
        seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1   # run id, monotone

        def tie_break(rows):
            m2 = next_pow2(m)
            pad = m2 - m

            def padded(x, fill):
                return jnp.concatenate([x, jnp.full((pad,), fill, jnp.int32)])

            payload = {
                "seg": padded(seg, INT32_MAX),
                "ranks": tuple(padded(r, 0) for r in rows[cr:ck]),
                "klass": padded(rows[ck], 0),
                "slot": jnp.arange(m2, dtype=jnp.int32),
            }

            def lt(a, b):
                seg_lt = a["seg"] < b["seg"]
                seg_eq = a["seg"] == b["seg"]
                ka = jnp.clip(a["klass"], 0, v - 1)
                kb = jnp.clip(b["klass"], 0, v - 1)
                ra = _pick(a["ranks"], lam_i1[ka, kb])
                rb = _pick(b["ranks"], lam_i2[ka, kb])
                rank_decides = seg_eq & (ra != rb)
                # slot order within a run == gidx order (gidx was a sort key)
                return jnp.where(
                    rank_decides, ra < rb,
                    jnp.where(seg_eq, a["slot"] < b["slot"], seg_lt))

            out = bitonic_sort(payload, lt)
            # pad slots (seg=MAX) sort last
            return take_cols(rows, out["slot"][:m])

        has_real_tie = jnp.any((~boundary) & (rows[0] == 0))
        return jax.lax.cond(has_real_tie, tie_break, lambda r: r, rows)

    return local_sort


# --------------------------------------------------------------------------
# Algorithm 2 body
# --------------------------------------------------------------------------
def psort_shard_body(
    rows: jnp.ndarray,           # int32[W, m_local] (rows as columns)
    *,
    p: int,
    axis: str,
    lt_fn=None,
    local_sort=None,
):
    """Body to be run inside shard_map. Returns globally sorted, block-
    balanced rows int32[W, m_local] (pads last globally), plus this shard's
    local overflow flag (callers MUST gather it across shards and raise —
    see `repro.bsp.exchange`)."""
    if lt_fn is None:
        lt_fn = lex_lt_full
    if local_sort is None:
        local_sort = local_sort_lex
    W, m = rows.shape

    # --- 1. local sort ---
    rows = local_sort(rows)
    nvalid = jnp.sum((rows[0] == 0).astype(jnp.int32))

    # --- 2. p+1 equally spaced primary samples (incl. min/max) ---
    t = jnp.arange(p + 1, dtype=jnp.int32)
    samp_idx = jnp.where(
        nvalid > 0,
        (t.astype(jnp.int64) * jnp.maximum(nvalid - 1, 0) // p).astype(jnp.int32),
        0)
    primary = take_cols(rows, samp_idx)                        # [W, p+1]
    primary = jnp.where((nvalid > 0), primary, make_pad_rows(p + 1, W))

    # --- 3. gather all p(p+1) samples everywhere (designated-processor step
    #        replicated: same h, fewer supersteps — DESIGN §3) ---
    all_samples = jax.lax.all_gather(primary, axis)            # [p, W, p+1]
    all_samples = all_samples.transpose(1, 0, 2).reshape(W, p * (p + 1))
    all_samples = sort_few(all_samples, lt_fn)
    ns = jnp.sum((all_samples[0] == 0).astype(jnp.int32))

    # --- 4. p-1 secondary splitters → p buckets ---
    tt = jnp.arange(1, p, dtype=jnp.int32)
    sec_idx = jnp.where(
        ns > 0,
        (tt.astype(jnp.int64) * jnp.maximum(ns - 1, 0) // p).astype(jnp.int32),
        0)
    splitters = take_cols(all_samples, sec_idx)                # [W, p-1]

    valid = rows[0] == 0
    dest = searchsorted_rows(splitters, rows, lt_fn=lt_fn)     # [m] ∈ [0,p)
    dest = jnp.clip(dest, 0, p - 1)

    # --- 5. bucket exchange (2 supersteps) + local sort ---
    cap_out = 2 * m + 2 * p + 4
    got, got_valid, over1 = exchange(rows, dest, valid, p=p, cap_out=cap_out,
                                     axis=axis)
    got = jnp.where(got_valid[None, :], got, make_pad_rows(cap_out, W))
    got = local_sort(got)

    # --- 6. rebalance to exactly m rows per shard, preserving global order ---
    # shard d takes global positions [d·m, (d+1)·m); the exchange keeps
    # each source's order and sources arrive in shard order, so the rows
    # arrive sorted and the valid ones fill a prefix.
    cnt = jnp.sum(got_valid.astype(jnp.int32))
    counts = jax.lax.all_gather(cnt[None], axis).reshape(p)
    offset = jnp.cumsum(counts) - counts
    my_off = offset[jax.lax.axis_index(axis)]
    gpos = my_off + jnp.arange(cap_out, dtype=jnp.int32)
    v2 = got[0] == 0
    dest2 = jnp.clip(gpos // m, 0, p - 1)
    out, out_valid, over2 = exchange(got, dest2, v2, p=p, cap_out=m,
                                     axis=axis)
    out = jnp.where(out_valid[None, :], out, make_pad_rows(m, W))
    return out, (over1 | over2)


def run_psort(mesh, axis: str, rows_global, *, lt_fn=None, local_sort=None,
              check: bool = True):
    """Convenience wrapper: jit(shard_map(psort_shard_body)) over a 1-D mesh.

    rows_global: int32[p*m, W] sharded (or shardable) on dim 0; the body
    sees each shard's rows as columns, and so do `lt_fn` and `local_sort`.
    Returns
    (rows_sorted, over bool[p]); raises RuntimeError when any shard's
    exchange overflowed (pass ``check=False`` to inspect the flags instead).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    p = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))

    @functools.partial(jax.jit, out_shardings=(
        NamedSharding(mesh, P(axis)), NamedSharding(mesh, P(axis))))
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(axis),),
        out_specs=(P(axis), P(axis)))
    def fn(rows):
        out, over = psort_shard_body(rows.T, p=p, axis=axis, lt_fn=lt_fn,
                                     local_sort=local_sort)
        return out.T, over[None]

    out, over = fn(rows_global)
    if check and bool(np.asarray(over).any()):
        raise RuntimeError(
            "psort exchange capacity overflow — the deterministic two-hop "
            "caps were exceeded (bug in the cap_out bound, not bad input)")
    return out, over
