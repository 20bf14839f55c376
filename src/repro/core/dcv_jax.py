"""Vectorised single-device JAX DC-v suffix array construction.

Same mathematics as `seq_ref` (difference-cover sampling + Lemma-1
comparisons), reorganised so that each recursion level is dominated by ONE
multi-key sort instead of an O(n log² n) comparator network:

* the v-character windows of ALL n_v positions are sorted once per level;
  the sample super-character ranks of Step 1 fall out of that order by
  filtering it to sample positions (a stable subsequence of a sorted
  sequence is sorted), and the same order is the Steps 2–4 candidate;
* suffix pairs sharing their full v-prefix form *tie groups*; only those
  are resolved with the paper's Lemma-1 comparator
  `rank[i + Λ[k_i][k_j]]`, evaluated on a compacted payload. For realistic
  alphabets the tie set is tiny (expected O(n²/σᵛ) positions), so the
  comparator now touches thousands of rows, not all n — see
  docs/architecture.md for the measured effect. Adversarial inputs
  (periodic / tiny alphabets) are first shrunk by stride-doubling
  refinement rounds so the comparator never sees a large payload.

The sort primitive itself is pluggable (`sort_impl`), because the fastest
correct choice is platform-dependent (see `repro.core.compat`):

==========  =============================================================
"auto"      `compat.default_sort_impl()`: "radix" on CPU, "lax" on TPU/GPU.
"radix"     host-side packed-key sorts: window columns are packed into as
            few 64-bit words as their bit-width allows (streamed off the
            text — the [n, v] window matrix is never materialised), then
            sorted with numpy's introsort (single word) or stable LSD
            passes (multi-word).
"lax"       XLA's `lax.sort` on the device: the windows are packed into
            uint32 key lanes and ordered by `lsd_sort.lsd_argsort`
            (single-operand radix passes, which compile several times
            faster on a TPU than one multi-operand sort) — the
            accelerator path.
"bitonic"   the legacy fully-fused comparator-bitonic network over all n_v
            payload rows (O(n log² n) compare-exchanges). Kept as an
            executable reference and for `benchmarks/sa_throughput.py`
            regression records.
"pallas"    the Mosaic kernels in `repro.kernels` (row bitonic sort +
            `dense_rank_sorted`); compiled on TPU, `interpret=True`
            elsewhere (correct but slow — CI exercises it at small n).
==========  =============================================================

Shapes are quantised to a geometric bucket grid (`pad_bucket`) when
`bucket=True` so repeated builds of nearby lengths reuse every jitted
computation; `TRACE_COUNTS` records one event per actual jax trace, which
the cache tests in `tests/api/test_sort_impl.py` assert against. The
recursion driver stays in Python (shapes are data-independent functions of
the schedule); each level and each of its phases runs under a host span of
`repro.core.spans`, which the profiler records beside the device.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .bitonic import bitonic_sort, lex_lt_int, next_pow2, sort_rows_with_index
from .compat import default_sort_impl, pallas_available
from .difference_cover import cover_tables
from . import lsd_sort
from .oracle import suffix_array_doubling
from .seq_ref import accelerated_next_v
from .spans import span

INT32_MAX = np.int32(np.iinfo(np.int32).max)

#: accepted `sort_impl` values ("auto" resolves via `compat.default_sort_impl`).
SORT_IMPLS = ("auto", "radix", "lax", "bitonic", "pallas")

#: jitted-piece trace counter: name -> number of times jax *traced* (not ran)
#: that piece. A second build of the same bucketed shape must not add events;
#: `tests/api/test_sort_impl.py` enforces it.
TRACE_COUNTS: collections.Counter = collections.Counter()


def trace_events() -> int:
    """Total number of jax traces performed by this module (and the
    `lsd_sort` argsort it calls) so far."""
    return sum(TRACE_COUNTS.values()) + sum(lsd_sort.TRACE_COUNTS.values())


def resolve_sort_impl(sort_impl: str) -> str:
    """Validate `sort_impl` and resolve "auto" for the current platform."""
    if sort_impl not in SORT_IMPLS:
        raise ValueError(f"unknown sort_impl {sort_impl!r}; "
                         f"expected one of {SORT_IMPLS}")
    return default_sort_impl() if sort_impl == "auto" else sort_impl


# --------------------------------------------------------------------------
# shape bucketing — the compiled-builder cache's padding rule
# --------------------------------------------------------------------------
#: lengths below this are never bucketed (trace cost is negligible there).
_BUCKET_MIN = 512


def pad_bucket(n: int) -> int:
    """Smallest grid length ≥ n, grid = {2^k · q/4 : q ∈ {4,5,6,7}}.

    Quantising every level's length to this geometric grid (ratio ≤ 1.25,
    so ≤ 25% padding overhead) collapses the open-ended family of input
    lengths onto O(log n) distinct shapes, so jax's jit cache — and the
    builder cache in `repro.api.build` — get hits instead of re-traces when
    serving many nearby lengths.
    """
    if n <= _BUCKET_MIN:
        return n
    base = 1 << (n - 1).bit_length() - 1          # largest power of two < n
    for q in (4, 5, 6, 7):
        cand = base * q // 4
        if cand >= n:
            return cand
    return base * 2


# --------------------------------------------------------------------------
# per-level constants (shared across builds; part of the builder-cache
# contract in repro.api.build)
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=256)
def _level_constants(n_v: int, v: int):
    """Host- and device-side constants for one (n_v, v) level shape.

    Returns (sample_pos int64[m], inv_sample int64[n_v], in_D bool[v],
    shifts int64[v,|D|], lam1/lam2 np int64[v,v], lam1/lam2 jnp int32[v,v]).
    lru-cached so repeated bucketed builds skip both the table construction
    and the host→device copies.
    """
    tabs = cover_tables(v)
    per_block = n_v // v
    sample_pos = (np.asarray(tabs.D, np.int64)[:, None]
                  + np.arange(per_block, dtype=np.int64)[None, :] * v
                  ).reshape(-1)
    inv_sample = np.full(n_v, -1, dtype=np.int64)
    inv_sample[sample_pos] = np.arange(len(sample_pos), dtype=np.int64)
    return (
        sample_pos,
        inv_sample,
        np.asarray(tabs.in_D, bool),
        np.asarray(tabs.shifts, np.int64),
        np.asarray(tabs.lam_idx1, np.int64),
        np.asarray(tabs.lam_idx2, np.int64),
        jnp.asarray(tabs.lam_idx1, jnp.int32),
        jnp.asarray(tabs.lam_idx2, jnp.int32),
    )


# --------------------------------------------------------------------------
# pluggable window-sort primitives
# --------------------------------------------------------------------------
def _window_words(xp: np.ndarray, n_v: int, v: int, lo: int, hi: int):
    """Pack the v-character windows at positions [0, n_v) into uint64 words.

    Values (in [lo, hi]; lo < 0 covers the distinct pad sentinels) are
    shifted to non-negative and packed most-significant-column-first,
    `64 // bits` columns per word, so that comparing the word list
    lexicographically equals comparing windows lexicographically. The words
    are built by strided reads straight off the padded text — the [n_v, v]
    window matrix is never materialised.
    """
    bits = max(1, int(hi - lo).bit_length())
    per_word = max(1, 64 // bits)
    shift = np.uint64(bits)
    words = []
    for start in range(0, v, per_word):
        w = np.zeros(n_v, dtype=np.uint64)
        for c in range(start, min(start + per_word, v)):
            w = (w << shift) | (xp[c:c + n_v] - lo).astype(np.uint64)
        words.append(w)
    return words


def _order_from_words(words):
    """Lexicographic argsort of packed word lists, MSD with compaction.

    One introsort on the most-significant word orders almost everything for
    high-entropy alphabets; later words only re-sort the (compacted) runs
    that are still tied — far cheaper than LSD's full-length stable passes.
    Returns (perm int64[N], is_start bool[N]): `is_start` marks the row-
    equality run boundaries along perm, which callers reuse as the tie-group
    seed (ties may land in any order inside a run).
    """
    perm = np.argsort(words[0]).astype(np.int64)
    n = len(perm)
    is_start = np.ones(n, dtype=bool)
    sw = words[0][perm]
    if n > 1:
        is_start[1:] = sw[1:] != sw[:-1]
    for w in words[1:]:
        start_slot = np.flatnonzero(is_start)
        run_id = np.cumsum(is_start) - 1
        sizes = np.diff(start_slot, append=n)
        sl = np.flatnonzero(sizes[run_id] > 1)
        if len(sl) == 0:
            break
        p = perm[sl]
        rid = run_id[sl]
        local = np.lexsort((w[p], rid))
        perm[sl] = p[local]
        wv = w[perm[sl]]
        if len(sl) > 1:
            is_start[sl[1:]] = (rid[1:] != rid[:-1]) | (wv[1:] != wv[:-1])
    return perm, is_start


def _window_lanes(xp: np.ndarray, n_v: int, v: int, hi: int):
    """Pack the v-character windows at positions [0, n_v) into uint32 key
    lanes for `lsd_sort.lsd_argsort`: (lanes uint32[L, n_v], bits of each
    lane).

    Text characters map to c + 1 and every pad sentinel (< 0) to 0, packed
    most-significant-column-first at `bit_length(hi + 1)` bits. For a
    window that starts in the text this orders exactly like the distinct
    sentinels do — its first pad column meets a text character (> 0) in
    any other window, never another pad — so ties are unchanged. Windows
    that start in the pad region all pack to 0; the caller puts them in
    their sentinel order (descending position) after the sort."""
    b = max(1, (hi + 1).bit_length())
    per = max(1, 32 // b)
    lanes, bits = [], []
    for start in range(0, v, per):
        lane = np.zeros(n_v, np.uint32)
        for c in range(start, min(start + per, v)):
            col = xp[c:c + n_v]
            lane = (lane << np.uint32(b)) | np.where(col >= 0, col + 1, 0
                                                     ).astype(np.uint32)
        lanes.append(lane)
        bits.append(b * (min(start + per, v) - start))
    return np.stack(lanes), bits


def _argsort_rows_pallas(rows: np.ndarray) -> np.ndarray:
    """Row sort on the Pallas bitonic kernel: append an index column (total
    order), pad to a power of two with +inf rows, sort, read the index."""
    from ..kernels.ops import bitonic_sort as kernel_bitonic_sort
    n, w = rows.shape
    n2 = next_pow2(n)
    body = np.concatenate(
        [rows.astype(np.int32), np.arange(n, dtype=np.int32)[:, None]],
        axis=1)
    if n2 > n:
        pad = np.full((n2 - n, w + 1), INT32_MAX, dtype=np.int32)
        body = np.concatenate([body, pad], axis=0)
    out = kernel_bitonic_sort(jnp.asarray(body), num_keys=w + 1,
                              interpret=not pallas_available())
    perm = np.asarray(out)[:, -1]
    return perm[perm < n].astype(np.int64)


def _window_order(xp: np.ndarray, n_v: int, v: int, lo: int, hi: int,
                  impl: str):
    """Sort all n_v window rows with the chosen impl.

    Returns (order int64[n_v], rep, is_start bool[n_v]): `rep` is a list of
    position-indexed arrays whose element-wise equality equals full-row
    equality — packed words for "radix", the raw shifted columns otherwise;
    `is_start` marks the row-equality run boundaries along `order`.
    """
    if impl == "radix":
        with span("dcv.pack"):
            words = _window_words(xp, n_v, v, lo, hi)
        with span("dcv.sort"):
            order, is_start = _order_from_words(words)
        return order, words, is_start
    with span("dcv.pack"):
        cols = [np.ascontiguousarray(xp[c:c + n_v]) for c in range(v)]
        if impl != "pallas":
            lanes, bits = _window_lanes(xp, n_v, v, hi)
    with span("dcv.sort"):
        if impl == "pallas":
            order = _argsort_rows_pallas(np.stack(cols, axis=1))
        else:
            order = np.asarray(lsd_sort.lsd_argsort(lanes, bits)).astype(
                np.int64)
            # pad-region windows packed to 0 sort first, ascending; their
            # sentinel order is descending position
            n_pad = int(np.count_nonzero(cols[0] < 0))
            order[:n_pad] = order[:n_pad][::-1]
    with span("dcv.runs"):
        is_start = np.ones(n_v, dtype=bool)
        if n_v > 1:
            is_start[1:] = _rows_neq(cols, order[1:], order[:-1])
    return order, cols, is_start


def _rows_neq(rep, pa: np.ndarray, pb: np.ndarray) -> np.ndarray:
    """Element-wise "window at pa differs from window at pb" via `rep`."""
    neq = rep[0][pa] != rep[0][pb]
    for w in rep[1:]:
        neq |= w[pa] != w[pb]
    return neq


# --------------------------------------------------------------------------
# prefix-doubling base case (also the "oracle" spine) — kept jitted for the
# lax/pallas paths; the radix path uses the host doubling reference.
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n", "steps"))
def suffix_array_doubling_jax(x: jnp.ndarray, n: int, steps: int) -> jnp.ndarray:
    """Prefix-doubling base case (Manber–Myers), log n rounds of lax.sort."""
    # saca-lint: allow[TRACE001] deliberate: trace-time retrace counter, mutated only while tracing, read by tests via total_traces()
    TRACE_COUNTS["doubling_jax"] += 1
    idx = jnp.arange(n, dtype=jnp.int32)
    x = x.astype(jnp.int32)

    def dense_rank(k1, k2):
        _, _, perm = jax.lax.sort((k1, k2, idx), num_keys=3)
        s1, s2 = k1[perm], k2[perm]
        boundary = jnp.ones(n, dtype=jnp.int32)
        if n > 1:
            neq = (s1[1:] != s1[:-1]) | (s2[1:] != s2[:-1])
            boundary = boundary.at[1:].set(neq.astype(jnp.int32))
        ranks_sorted = jnp.cumsum(boundary) - 1
        rank = jnp.zeros(n, dtype=jnp.int32).at[perm].set(ranks_sorted)
        return rank, perm

    rank, perm = dense_rank(x, jnp.zeros_like(x))
    for s in range(steps):
        h = 1 << s
        shifted = jnp.concatenate([rank[h:], jnp.full((min(h, n),), -1, jnp.int32)])[:n]
        rank, perm = dense_rank(rank, shifted)
    return perm


def _suffix_array_base(x_np: np.ndarray, impl: str) -> np.ndarray:
    """Recursion cutoff: sort a short text directly by prefix doubling."""
    n = len(x_np)
    if impl == "radix":
        return suffix_array_doubling(x_np.astype(np.int64)).astype(np.int32)
    steps = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    return np.asarray(
        suffix_array_doubling_jax(jnp.asarray(x_np, jnp.int32), n, steps))


# --------------------------------------------------------------------------
# legacy fully-fused bitonic path (sort_impl="bitonic")
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("v", "m"))
def _encode_sample(xp: jnp.ndarray, sample_pos: jnp.ndarray, v: int, m: int):
    """Step 1 (first half): rank super-characters; X' + distinct flag."""
    # saca-lint: allow[TRACE001] deliberate: trace-time retrace counter, mutated only while tracing, read by tests via total_traces()
    TRACE_COUNTS["encode_sample_lax"] += 1
    W = xp[sample_pos[:, None] + jnp.arange(v, dtype=jnp.int32)[None, :]]
    perm = sort_rows_with_index(W, v)
    Ws = W[perm]
    boundary = jnp.ones(m, dtype=jnp.int32)
    if m > 1:
        boundary = boundary.at[1:].set(
            jnp.any(Ws[1:] != Ws[:-1], axis=1).astype(jnp.int32))
    ranks_sorted = jnp.cumsum(boundary) - 1
    Xp = jnp.zeros(m, dtype=jnp.int32).at[perm].set(ranks_sorted)
    sa_rank_direct = jnp.zeros(m, dtype=jnp.int32).at[perm].set(
        jnp.arange(m, dtype=jnp.int32))
    distinct = jnp.all(boundary == 1)
    return Xp, distinct, sa_rank_direct


@functools.partial(jax.jit, static_argnames=("v", "n_v"))
def _fused_final_sort(
    xp: jnp.ndarray,
    sample_pos: jnp.ndarray,
    sa_rank: jnp.ndarray,
    shifts_tab: jnp.ndarray,     # int32[v, |D|]
    lam_i1: jnp.ndarray,         # int32[v, v]
    lam_i2: jnp.ndarray,         # int32[v, v]
    v: int,
    n_v: int,
) -> jnp.ndarray:
    """Fused Steps 2–4: one comparator-bitonic sort of all n_v suffixes.

    O(n log² n) compare-exchanges over the full payload — kept as the
    executable reference the keyed paths are tested against, and as the
    `sort_impl="bitonic"` regression row in BENCH_sa_throughput.json.
    """
    # saca-lint: allow[TRACE001] deliberate: trace-time retrace counter, mutated only while tracing, read by tests via total_traces()
    TRACE_COUNTS["fused_final_sort_bitonic"] += 1
    dsize = shifts_tab.shape[1]
    rank = jnp.full(n_v + v, -1, dtype=jnp.int32).at[sample_pos].set(sa_rank)

    pos = jnp.arange(n_v, dtype=jnp.int32)
    chars = xp[pos[:, None] + jnp.arange(v, dtype=jnp.int32)[None, :]]
    klass = pos % v
    rvals = rank[pos[:, None] + shifts_tab[klass]]          # [n_v, |D|]

    n2 = next_pow2(n_v)
    pad = n2 - n_v
    payload = {
        "chars": jnp.concatenate(
            [chars, jnp.full((pad, v), INT32_MAX, jnp.int32)], axis=0),
        "ranks": jnp.concatenate(
            [rvals, jnp.zeros((pad, dsize), jnp.int32)], axis=0),
        "klass": jnp.concatenate(
            [klass, jnp.zeros((pad,), jnp.int32)], axis=0),
        "idx": jnp.concatenate(
            [pos, n_v + jnp.arange(pad, dtype=jnp.int32)], axis=0),
    }

    def lt_fn(a, b):
        char_lt, char_eq = lex_lt_int(a["chars"], b["chars"])
        ka, kb = a["klass"], b["klass"]
        ra = jnp.take_along_axis(a["ranks"], lam_i1[ka, kb][:, None], axis=1)[:, 0]
        rb = jnp.take_along_axis(b["ranks"], lam_i2[ka, kb][:, None], axis=1)[:, 0]
        rank_decides = char_eq & (ra != rb)
        return jnp.where(
            rank_decides, ra < rb,
            jnp.where(char_eq, a["idx"] < b["idx"], char_lt))

    out = bitonic_sort(payload, lt_fn)
    return out["idx"][:n_v]   # pads carry INT32_MAX chars → sorted last


# --------------------------------------------------------------------------
# Lemma-1 tie resolution for the keyed paths
# --------------------------------------------------------------------------
@jax.jit
def _lambda_tiebreak_jit(seg, rvals, klass, pos, lam_i1, lam_i2):
    """Sort the compacted tie payload by (tie group, Lemma-1 rank, index).

    All rows inside one `seg` group share their full v-character prefix, so
    the paper's Lemma-1 comparison degenerates to a pure rank lookup:
    `rank[i + Λ[k_i][k_j]]` via the per-class local index tables. Pad rows
    carry seg=INT32_MAX and sort to the back. Callers pad to powers of two,
    so the jit cache holds at most log₂(n) entries.
    """
    # saca-lint: allow[TRACE001] deliberate: trace-time retrace counter, mutated only while tracing, read by tests via total_traces()
    TRACE_COUNTS["lambda_tiebreak"] += 1
    payload = {"seg": seg, "ranks": rvals, "klass": klass, "idx": pos}

    def lt_fn(a, b):
        seg_lt = a["seg"] < b["seg"]
        seg_eq = a["seg"] == b["seg"]
        ka, kb = a["klass"], b["klass"]
        ra = jnp.take_along_axis(a["ranks"], lam_i1[ka, kb][:, None], axis=1)[:, 0]
        rb = jnp.take_along_axis(b["ranks"], lam_i2[ka, kb][:, None], axis=1)[:, 0]
        rank_decides = seg_eq & (ra != rb)
        return jnp.where(rank_decides, ra < rb,
                         jnp.where(seg_eq, a["idx"] < b["idx"], seg_lt))

    return bitonic_sort(payload, lt_fn)["idx"]


#: a residue whose widest tie group needs more lanes than this runs on the
#: jitted device network; otherwise (the overwhelmingly common case) the
#: same bitonic schedule runs lane-parallel in numpy, each group at its own
#: power-of-two width, skipping the device round-trip entirely.
_HOST_LANE_MAX = 16


def _lambda_tiebreak_host(p, lane, row_of, n_rows, g2, rvals, klass,
                          lam1_np, lam2_np) -> np.ndarray:
    """Lane-parallel bitonic over [n_rows, g2] tie groups, vectorised in
    numpy: one compare-exchange stage = one vectorised Lemma-1 comparator
    evaluation across every group at once. Pads (-1) act as +inf."""
    mat = np.full((n_rows, g2), -1, dtype=np.int64)
    mat[row_of, lane] = np.arange(len(p), dtype=np.int64)
    idxv = p

    def lt(a, b):
        ac = np.clip(a, 0, None)
        bc = np.clip(b, 0, None)
        ka, kb = klass[ac], klass[bc]
        ra = rvals[ac, lam1_np[ka, kb]]
        rb = rvals[bc, lam2_np[ka, kb]]
        res = np.where(ra != rb, ra < rb, idxv[ac] < idxv[bc])
        return np.where(a < 0, False, np.where(b < 0, True, res))

    lanes = np.arange(g2)
    k = 2
    while k <= g2:
        j = k // 2
        while j >= 1:
            partner = lanes ^ j
            other = mat[:, partner]
            up = (lanes & k) == 0
            lower = lanes < partner
            keep = (lt(mat, other) == lower[None, :]) == up[None, :]
            mat = np.where(keep, mat, other)
            j //= 2
        k *= 2
    return p[mat[mat >= 0]]          # row-major: groups in slot order


def _lambda_tiebreak_bucketed(p, lane, row_of, n_rows, rvals, klass,
                              lam1_np, lam2_np) -> tuple[np.ndarray, int]:
    """`_lambda_tiebreak_host` with each tie group at its own width.

    Groups are bucketed by next_pow2(size) and each width class runs the
    network alone, so a residue of pairs pays one stage, not the widest
    group's schedule. The comparator is a total order (index tie-break),
    so the result is the single-width network's. A class's groups keep
    their slot order, so its result lands back on its own slots. Returns
    the permutation and the lanes each stage evaluates, summed over the
    classes (groups × width)."""
    sizes = np.bincount(row_of, minlength=n_rows)
    widest = int(sizes.max())
    out = np.empty_like(p)
    slots = 0
    lo, width = 0, 2
    while lo < widest:
        in_class = (sizes > lo) & (sizes <= width)
        n_class = int(in_class.sum())
        if n_class:
            sel = in_class[row_of]
            class_row = np.cumsum(in_class) - 1
            out[sel] = _lambda_tiebreak_host(
                p[sel], lane[sel], class_row[row_of[sel]], n_class, width,
                rvals[sel], klass[sel], lam1_np, lam2_np)
            slots += n_class * width
        lo, width = width, 2 * width
    return out, slots


# --------------------------------------------------------------------------
# keyed final phase (sort_impl = "radix" / "lax" / "pallas")
# --------------------------------------------------------------------------
#: tie sets larger than max(this, n_v/8) are first shrunk by stride-doubling
#: refinement rounds before any comparator runs — keeps adversarial inputs
#: (tiny alphabets, periodic texts) off the O(U log² U) network.
_TIEBREAK_COMPACT_MAX = 1024


def _resolve_ties(order, is_start, rank, shifts_np, lam1_np, lam2_np,
                  lam1_jnp, lam2_jnp, v: int, n_v: int) -> np.ndarray:
    """Steps 2–4 second half: refine the window-sorted candidate order.

    `order` sorts all n_v suffixes by their v-character window; `is_start`
    marks tie-group boundaries along it. While the tie set is large
    (adversarial inputs), stride-doubling refinement rounds shrink it using
    the group ranks themselves as keys (classic Manber–Myers, seeded at
    resolution v); the residue is resolved by the Lemma-1 comparator on a
    compacted payload — lane-parallel in numpy when no group is wider than
    `_HOST_LANE_MAX`, each group sorted at its own power-of-two width, else
    the jitted bitonic network over the whole residue.
    """
    def run_state(is_start):
        start_slot = np.flatnonzero(is_start)
        run_id = np.cumsum(is_start) - 1                  # per slot
        r_sorted = start_slot[run_id]                     # rank-with-ties
        sizes = np.diff(start_slot, append=n_v)
        return start_slot, run_id, r_sorted, sizes

    start_slot, run_id, r_sorted, sizes = run_state(is_start)
    r_pos = np.empty(n_v, dtype=np.int64)
    r_pos[order] = r_sorted
    unresolved = sizes[run_id] > 1
    U = int(unresolved.sum())
    if U == 0:
        return order

    # Refinement: slots in one run share their first `stride` characters,
    # so (r_pos[i], r_pos[i+stride]) is a valid refinement key.
    stride = v
    cap = max(_TIEBREAK_COMPACT_MAX, n_v >> 3)
    while U > cap and stride < n_v:
        with span("dcv.refine", ties=U):
            sl = np.flatnonzero(unresolved)
            p = order[sl]
            nxt = p + stride
            key = np.where(nxt < n_v, r_pos[np.minimum(nxt, n_v - 1)], -1)
            packed = (r_pos[p] << 32) | (key + 1)         # both < 2^31
            local = np.argsort(packed, kind="stable")
            order[sl] = p[local]
            pk = packed[local]
            if len(sl) > 1:
                # run starts re-emerge via the high bits; interiors refine.
                is_start[sl[1:]] = pk[1:] != pk[:-1]
            start_slot, run_id, r_sorted, sizes = run_state(is_start)
            r_pos[order] = r_sorted
            unresolved = sizes[run_id] > 1
            U = int(unresolved.sum())
            stride *= 2
    if U == 0:
        return order

    # Lemma-1 comparator on the compacted ties only.
    sl = np.flatnonzero(unresolved)
    p = order[sl]
    klass = p % v
    rvals = rank[p[:, None] + shifts_np[klass]]
    lane = sl - start_slot[run_id[sl]]
    g2 = next_pow2(int(lane.max()) + 1)
    if g2 <= _HOST_LANE_MAX:
        with span("dcv.lemma1", ties=U, width=g2, path="host") as sp:
            rows, row_of = np.unique(run_id[sl], return_inverse=True)
            order[sl], slots = _lambda_tiebreak_bucketed(
                p, lane, row_of, len(rows), rvals, klass, lam1_np, lam2_np)
            sp.set_metadata(slots=slots)
        return order

    n2 = next_pow2(U)
    seg_p = np.full(n2, INT32_MAX, dtype=np.int32)
    rv_p = np.zeros((n2, shifts_np.shape[1]), dtype=np.int32)
    kl_p = np.zeros(n2, dtype=np.int32)
    pos_p = np.full(n2, INT32_MAX, dtype=np.int32)
    seg_p[:U] = r_pos[p]
    rv_p[:U] = rvals
    kl_p[:U] = klass
    pos_p[:U] = p
    with span("dcv.lemma1", ties=U, width=g2, path="device"):
        out = np.asarray(_lambda_tiebreak_jit(
            jnp.asarray(seg_p), jnp.asarray(rv_p), jnp.asarray(kl_p),
            jnp.asarray(pos_p), lam1_jnp, lam2_jnp))
    order[sl] = out[:U]
    return order


# --------------------------------------------------------------------------
# recursion driver
# --------------------------------------------------------------------------
def suffix_array_jax(
    x,
    v: int = 3,
    schedule=accelerated_next_v,
    base_threshold: int | None = None,
    sort_impl: str = "auto",
    bucket: bool = False,
) -> np.ndarray:
    """Suffix array of x (ints ≥ 0, < 2³¹) — vectorised JAX DC-v.

    Parameters
    ----------
    x : 1-D integer sequence (tokens / bytes).
    v : initial difference-cover modulus (paper Algorithm 1).
    schedule : ``(v, |D|, m) -> v'`` — the paper's accelerated v-schedule
        by default.
    base_threshold : recursion cutoff; below it a prefix-doubling sort runs
        directly. ``None`` picks the impl's tuned default (radix: 1024 —
        the host doubling base beats 2-3 more tiny DC levels; others: 256).
    sort_impl : one of `SORT_IMPLS`; see the module docstring.
    bucket : pad every level's length up to the `pad_bucket` grid so
        repeated builds of nearby lengths reuse all jitted computations
        (`repro.api.build` enables this for its builder cache).

    Returns np.int32[n], a permutation of range(n).
    """
    impl = resolve_sort_impl(sort_impl)
    if base_threshold is None:
        base_threshold = 1024 if impl == "radix" else 256
    x = np.asarray(x)
    n = int(len(x))
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    if n == 1:
        return np.zeros(1, dtype=np.int32)

    def rec(x_np: np.ndarray, v: int, level: int = 0) -> np.ndarray:
        n = len(x_np)
        if n <= max(base_threshold, v, 4):
            with span("dcv.base"):
                return _suffix_array_base(x_np, impl)
        n_b = pad_bucket(n) if bucket else n
        v = int(min(max(v, 3), n_b))
        n_v = v * int(np.ceil(n_b / v))
        with span("dcv.level", level=level, n_v=n_v, v=v):
            return rec_level(x_np, v, n_v, level)

    def rec_level(x_np: np.ndarray, v: int, n_v: int,
                  level: int) -> np.ndarray:
        """One level of `rec` above its base case."""
        n = len(x_np)
        tabs = cover_tables(v)
        # Pad with *distinct, decreasing* negative sentinels. Distinctness
        # matters: equal sentinels would form giant tie groups and defeat
        # the `distinct` recursion short-circuit once bucketing makes the
        # pad region large. Correctness needs only "below the alphabet":
        # the first differing window column between two real suffixes is
        # never pad-vs-pad (pad values are position-unique), so the
        # sentinels' relative order never decides a real comparison.
        with span("dcv.pack"):
            xp_np = np.empty(n_v + 2 * v, dtype=np.int64)
            xp_np[:n] = x_np
            npad = n_v + 2 * v - n
            xp_np[n:] = -1 - np.arange(npad, dtype=np.int64)
            (sample_pos, inv_sample, in_D, shifts_np,
             lam1_np, lam2_np, lam1_jnp, lam2_jnp) = _level_constants(n_v, v)
            lo, hi = -npad, int(x_np.max(initial=0))
        m = len(sample_pos)

        if impl == "bitonic":
            with span("dcv.sort"):
                xp = jnp.asarray(xp_np, jnp.int32)
                sp_dev = jnp.asarray(sample_pos, jnp.int32)
                Xp_dev, distinct_dev, sa_rank_dev = _encode_sample(
                    xp, sp_dev, v, m)
                Xp = np.asarray(Xp_dev).astype(np.int64)
                distinct = bool(distinct_dev)
                sa_rank = np.asarray(sa_rank_dev).astype(np.int64)
            if not distinct:
                v_next = schedule(v, len(tabs.D), m)
                sa_sub = rec(Xp, v_next, level + 1)
                sa_rank = np.zeros(m, dtype=np.int64)
                sa_rank[sa_sub] = np.arange(m, dtype=np.int64)
            with span("dcv.sort"):
                sa_full = np.asarray(_fused_final_sort(
                    xp, sp_dev, jnp.asarray(sa_rank, jnp.int32),
                    jnp.asarray(tabs.shifts, jnp.int32),
                    lam1_jnp, lam2_jnp, v, n_v))
            return sa_full[sa_full < n]

        # --- keyed paths: ONE window sort feeds Step 1 AND Steps 2–4 ---
        order, rep, is_start = _window_order(xp_np, n_v, v, lo, hi, impl)

        # Step 1: sample ranks = the window order filtered to sample
        # positions (a stable subsequence of a sorted sequence is sorted).
        with span("dcv.rank") as rank_span:
            s_slots = np.flatnonzero(in_D[order % v])
            sp = order[s_slots]                   # sample pos, window-sorted
            si = inv_sample[sp]
            if impl == "pallas" and m > 1:
                from ..kernels.ops import dense_rank_sorted
                rows_s = np.stack([c[sp] for c in rep], axis=1)
                ranks_dev, _ = dense_rank_sorted(
                    jnp.asarray(rows_s, jnp.int32),
                    interpret=not pallas_available())
                ranks_sorted = np.asarray(ranks_dev).astype(np.int64)
                distinct = bool(ranks_sorted[-1] == m - 1)
            else:
                sb = np.ones(m, dtype=bool)
                if m > 1:
                    sb[1:] = _rows_neq(rep, sp[1:], sp[:-1])
                ranks_sorted = np.cumsum(sb) - 1
                distinct = bool(ranks_sorted[-1] == m - 1)
            rank_span.set_metadata(distinct=distinct)
            sa_rank = np.empty(m, dtype=np.int64)
            if distinct:
                sa_rank[si] = np.arange(m, dtype=np.int64)
            else:
                Xp = np.empty(m, dtype=np.int64)
                Xp[si] = ranks_sorted
        if not distinct:
            v_next = schedule(v, len(tabs.D), m)
            sa_sub = rec(Xp, v_next, level + 1)
            sa_rank[sa_sub] = np.arange(m, dtype=np.int64)

        # Steps 2–4: refine the shared window order with Lemma-1 ranks.
        with span("dcv.ties"):
            rank = np.full(n_v + v, -1, dtype=np.int64)
            rank[sample_pos] = sa_rank
            sa_full = _resolve_ties(order, is_start, rank, shifts_np,
                                    lam1_np, lam2_np, lam1_jnp, lam2_jnp, v,
                                    n_v)
        return sa_full[sa_full < n]

    return rec(x.astype(np.int64), v).astype(np.int32)
