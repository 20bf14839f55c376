"""Algorithm 3 — BSP parallel suffix array construction by accelerated
sampling, on a 1-D shard_map mesh.

Round structure (per recursion level i, modulus v = v_i, cover D = D_i):

  SM1  (11 supersteps): char halo → sample super-character windows →
       Algorithm-2 psort (key mode) → global dense rank (+ all-distinct
       flag) → route ranks to block-major X' layout.
  rec  : recurse on X' with v' = min(⌈v^{5/4}⌉, ⌈v²/|D|⌉−1, |X'|); base case
       (|X'| ≤ threshold ≈ n/p) gathers X' and solves with the single-device
       DC-v (the paper's "send to processor 0").
  SM2  (9 supersteps): route sample ranks back to position owners → rank/char
       halos → build self-contained Lemma-1 payloads → Algorithm-2 psort
       (the fused Steps 2–4, DESIGN §3.3) → SA.

The shard-local sorts inside both psorts are pluggable (`sort_impl`,
resolved by `repro.bsp.psort.resolve_bsp_sort_impl`): the default "radix"
path packs the SM1 super-character windows AND the SM2 Lemma-1 payload
characters into 30-bit int32 key lanes and key-sorts them with ONE key sort
per call (`repro.core.lsd_sort.lsd_argsort`; Lemma-1 comparisons only run
on equal-window runs, via a cond-gated bitonic pass); "lax" is the same two-phase sort on unpacked
columns; "bitonic" is the legacy full comparator network, kept as the
regression row of `benchmarks/bsp_throughput.py`.

All shapes are data-independent functions of (n, p, schedule): the index
domain is padded to n_pv = p·v·⌈n/(p·v)⌉ so every shard holds n_loc = n_pv/p
characters (a multiple of v) and exactly m_loc = |D|·n_loc/v sample windows.
Sentinel-pad suffixes sort first and are trimmed at the end.

Superstep accounting: the counts logged by `BSPCounters` (SM1 = 11, SM2 = 9
per round — `_round_cost`) match the collectives the code executes barrier
for barrier: SM1 = halo ppermute + 6 psort collectives + boundary ppermute
+ rank-offset all_gather + 2 routing all_to_alls; SM2 = 2 un-routing
all_to_alls + halo ppermute + 6 psort collectives. Diagnostic flags
(overflow, all-distinct) are computed shard-locally and gathered through
the stage outputs, so they add no barriers. `estimate_costs` replays the
same schedule analytically for arbitrary (n, p).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.difference_cover import cover_tables
from ..core.dcv_jax import suffix_array_jax
from ..core.seq_ref import accelerated_next_v
from .counters import BSPCounters, NULL_COUNTERS
from .exchange import exchange
from .psort import (make_local_sort_bitonic, make_local_sort_keyed,
                    make_payload_lt, pack_key_columns,
                    packed_width, psort_shard_body, quantize_sigma,
                    resolve_bsp_sort_impl)

INT32_MAX = np.int32(np.iinfo(np.int32).max)


# --------------------------------------------------------------------------
# round geometry
# --------------------------------------------------------------------------
def round_geometry(n: int, p: int, v: int):
    n_pv = p * v * math.ceil(n / (p * v))
    n_loc = n_pv // p
    tabs = cover_tables(v)
    dsize = len(tabs.D)
    m_loc = dsize * n_loc // v          # samples per shard == X' elems/shard
    m_tot = m_loc * p
    return n_pv, n_loc, m_loc, m_tot, tabs


# --------------------------------------------------------------------------
# SM1: sample sort + X' construction
# --------------------------------------------------------------------------
def pack_window_columns(win: jnp.ndarray, sigma: int):
    """Radix key packing for SM1 windows (§Perf SA-iteration A): characters
    are shifted +1 so the -1 sentinel packs as 0, then packed into 30-bit
    int32 lanes by `repro.bsp.psort.pack_key_columns` (order-preserving,
    injective). Cuts the sort/exchange width from v to ⌈v·bits/30⌉ lanes."""
    return pack_key_columns(win, -1, sigma)


def _class_index(tabs) -> jnp.ndarray:
    """int32[v]: the index of residue k in D, or -1 when k ∉ D."""
    d_idx = np.full(tabs.v, -1, np.int32)
    d_idx[list(tabs.D)] = np.arange(len(tabs.D), dtype=np.int32)
    return jnp.asarray(d_idx)


def _sm1_body(xloc, *, p, v, n_loc, m_loc, tabs, axis, sigma=None):
    dsize = len(tabs.D)
    me = jax.lax.axis_index(axis)

    # --- char halo: first v chars of next shard (last shard: sentinels) ---
    halo = jax.lax.ppermute(xloc[:v], axis, [(s, s - 1) for s in range(1, p)])
    halo = jnp.where(me == p - 1, jnp.full((v,), -1, jnp.int32), halo)
    xp = jnp.concatenate([xloc, halo])                      # [n_loc + v]

    # --- sample windows (block-local positions ≡ k (mod v), k ∈ D) ---
    D = jnp.asarray(tabs.D, jnp.int32)
    off = (D[:, None] + jnp.arange(n_loc // v, dtype=jnp.int32)[None, :] * v
           ).reshape(-1)                                    # [m_loc] local pos
    gpos = me.astype(jnp.int32) * n_loc + off
    win = jnp.stack([xp[off + k] for k in range(v)])        # [v, m_loc]
    if sigma is not None:
        win = pack_window_columns(win, sigma)
    w = win.shape[0]                       # packed key width ≤ v
    rows = jnp.concatenate([
        jnp.zeros((1, m_loc), jnp.int32), win, gpos[None]])

    # --- Algorithm 2 (key mode) ---
    rows, over = psort_shard_body(rows, p=p, axis=axis)

    # --- global dense rank of windows + distinct flag ---
    keys = rows[1:1 + w]
    prev_last = jax.lax.ppermute(keys[:, -1], axis,
                                 [(s, s + 1) for s in range(p - 1)])
    first_b = jnp.where(me == 0, True, jnp.any(keys[:, 0] != prev_last))
    b = jnp.concatenate([
        first_b[None], jnp.any(keys[:, 1:] != keys[:, :-1], axis=0)
    ]).astype(jnp.int32)
    loc_sum = jnp.sum(b)
    sums = jax.lax.all_gather(loc_sum[None], axis).reshape(p)
    offset = (jnp.cumsum(sums) - sums)[me]
    rank = offset + jnp.cumsum(b) - 1                       # dense global rank
    # shard-local "every window here started a run"; the driver ANDs the
    # per-shard flags — no pmin barrier needed.
    distinct = jnp.min(b) == 1

    # --- route (j, rank) to X' owners; j = block-major sample index ---
    d_idx = _class_index(tabs)
    g = rows[1 + w]                                         # gpos
    j = d_idx[g % v] * ((n_loc // v) * p) + g // v
    got, got_valid, over2 = exchange(
        jnp.stack([rank.astype(jnp.int32), j]), jnp.clip(j // m_loc, 0, p - 1),
        jnp.ones(m_loc, bool), p=p, cap_out=m_loc, axis=axis)
    # the one data-dependent permutation of the round: ranks arrive in
    # window order and go to their X' slots
    xprime = jnp.zeros(m_loc, jnp.int32).at[
        jnp.where(got_valid, got[1] % m_loc, m_loc)
    ].set(got[0], mode="drop")
    return xprime, distinct[None], (over | over2)[None]


# --------------------------------------------------------------------------
# SM2: rank un-routing + fused Lemma-1 payload sort
# --------------------------------------------------------------------------
def _sm2_body(xloc, sa_rank_loc, *, p, v, n_loc, m_loc, tabs, axis,
              impl="bitonic", sigma=None):
    dsize = len(tabs.D)
    me = jax.lax.axis_index(axis)
    D_np = np.asarray(tabs.D, np.int32)
    per_block = (n_loc // v) * p                            # block length in X'

    # --- route sample ranks back to position owners ---
    # Shard s needs, for each a ∈ D, the X' slice of the positions ≡ D[a]
    # (mod v) it owns: [a·per_block + s·n_loc/v, … + n_loc/v). Those slices
    # ascend in j and the exchange keeps order, so the ranks arrive as
    # rank_s[a·(n_loc/v) + o//v] for local position o ≡ D[a] (mod v).
    jloc = me.astype(jnp.int32) * m_loc + jnp.arange(m_loc, dtype=jnp.int32)
    blk = jloc // per_block                                  # index into D
    pos = jnp.asarray(D_np)[jnp.clip(blk, 0, dsize - 1)] + (jloc % per_block) * v
    dest = jnp.clip(pos // n_loc, 0, p - 1)
    got, _, over = exchange(
        sa_rank_loc[None].astype(jnp.int32), dest, jnp.ones(m_loc, bool),
        p=p, cap_out=m_loc, axis=axis)
    o = jnp.arange(n_loc, dtype=jnp.int32)
    a = _class_index(tabs)[o % v]
    rank_loc = jnp.where(
        a >= 0, got[0, jnp.maximum(a, 0) * (n_loc // v) + o // v], -1)

    # --- halos: rank (v) and chars (v) from next shard ---
    fwd = jnp.concatenate([rank_loc[:v], xloc[:v]])
    halo = jax.lax.ppermute(fwd, axis, [(s, s - 1) for s in range(1, p)])
    halo = jnp.where(me == p - 1, jnp.full((2 * v,), -1, jnp.int32), halo)
    rank_loc = jnp.concatenate([rank_loc, halo[:v]])         # [n_loc + v]
    xp = jnp.concatenate([xloc, halo[v:]])                   # [n_loc + v]

    # --- Lemma-1 payloads for ALL local suffixes ---
    offs = jnp.arange(n_loc, dtype=jnp.int32)
    gidx = me.astype(jnp.int32) * n_loc + offs
    chars = jnp.stack([xp[offs + k] for k in range(v)])      # [v, n_loc]
    klass = gidx % v
    rvals = jnp.stack([                                      # [|D|, n_loc]
        rank_loc[jnp.clip(offs + jnp.asarray(tabs.shifts[:, k])[klass], 0,
                          n_loc + v - 1)]
        for k in range(dsize)])

    lam_i1 = jnp.asarray(tabs.lam_idx1, jnp.int32)
    lam_i2 = jnp.asarray(tabs.lam_idx2, jnp.int32)
    # "bitonic" (legacy): the Lemma-1 comparator at every compare-exchange
    # of the local bitonic network, raw characters as the head. Keyed: pack
    # ("radix") or keep raw ("lax") the character head, key-sort it, and
    # resolve equal-window runs with the cond-gated Lemma-1 pass — see
    # repro.bsp.psort.make_local_sort_keyed.
    keys = pack_key_columns(chars, -1, sigma) if sigma is not None else chars
    nk = keys.shape[0]
    payload = jnp.concatenate([
        jnp.zeros((1, n_loc), jnp.int32), keys, rvals, klass[None],
        gidx[None]])
    lt = make_payload_lt(nk, v, dsize, lam_i1, lam_i2)
    local_sort = (make_local_sort_bitonic(lt) if impl == "bitonic" else
                  make_local_sort_keyed(nk, v, dsize, lam_i1, lam_i2))
    out, over2 = psort_shard_body(payload, p=p, axis=axis, lt_fn=lt,
                                  local_sort=local_sort)
    sa = out[2 + nk + dsize]                                 # gidx field
    return sa, (over | over2)[None]


# --------------------------------------------------------------------------
# jitted stage wrappers
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("p", "v", "n_loc", "m_loc", "vkey", "axis",
                              "mesh_holder", "sigma"))
def _sm1(xg, *, p, v, n_loc, m_loc, vkey, axis, mesh_holder, sigma=None):
    mesh = mesh_holder.mesh
    tabs = cover_tables(v)
    body = functools.partial(_sm1_body, p=p, v=v, n_loc=n_loc, m_loc=m_loc,
                             tabs=tabs, axis=axis, sigma=sigma)
    return shard_map(
        body, mesh=mesh, in_specs=(P(axis),),
        out_specs=(P(axis), P(axis), P(axis)))(xg)


@functools.partial(
    jax.jit, static_argnames=("p", "v", "n_loc", "m_loc", "vkey", "axis",
                              "mesh_holder", "impl", "sigma"))
def _sm2(xg, sa_rank, *, p, v, n_loc, m_loc, vkey, axis, mesh_holder,
         impl="bitonic", sigma=None):
    mesh = mesh_holder.mesh
    tabs = cover_tables(v)
    body = functools.partial(_sm2_body, p=p, v=v, n_loc=n_loc, m_loc=m_loc,
                             tabs=tabs, axis=axis, impl=impl, sigma=sigma)
    return shard_map(
        body, mesh=mesh, in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)))(xg, sa_rank)


class _MeshHolder:
    """Hashable wrapper so a Mesh can be a static jit arg."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __hash__(self):
        return hash(tuple(d.id for d in self.mesh.devices.flat)
                    + tuple(self.mesh.shape.items()))

    def __eq__(self, other):
        return isinstance(other, _MeshHolder) and hash(self) == hash(other)


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------
def _round_cost(label, n_loc, m_loc, p, v, dsize, W, counters):
    """Analytic per-superstep BSP costs for one SM stage (C4/C5)."""
    lb = int(math.ceil(math.log2(max(m_loc * 4, 2))))
    psort = [
        ("psort/sample_gather", p * (p + 1) * W, m_loc * W * lb),
        ("psort/a2a_hop1", m_loc * W, m_loc * W),
        ("psort/a2a_hop2", 2 * m_loc * W, m_loc * W),
        ("psort/count_gather", p, 2 * m_loc * W * lb),
        ("psort/rebal_hop1", 2 * m_loc * W, m_loc * W),
        ("psort/rebal_hop2", m_loc * W, m_loc * W * lb),
    ]
    if label == "SM1":
        steps = ([("halo", v, n_loc)] + psort
                 + [("rank/boundary", W, m_loc * W), ("rank/scan", p, m_loc),
                    ("route/a2a_hop1", 3 * m_loc, m_loc),
                    ("route/a2a_hop2", 3 * m_loc, m_loc)])
    else:
        steps = ([("unroute/a2a_hop1", 3 * m_loc, m_loc),
                  ("unroute/a2a_hop2", 3 * m_loc, m_loc),
                  ("halo", 2 * v, n_loc)] + psort)
    for name, h, w in steps:
        counters.superstep(f"{label}/{name}", h=h, w=w)


def _check_overflow(over, stage: str) -> None:
    """Turn a gathered per-shard overflow flag into a hard error."""
    if bool(np.asarray(over).any()):
        raise RuntimeError(
            f"BSP exchange capacity overflow in {stage}: the deterministic "
            f"two-hop caps were exceeded — a bug in the caller's cap_out "
            f"bound (see repro.bsp.exchange), never an input-data error")


def _sm_widths(v: int, sigma: int, impl: str, pack_keys: bool):
    """(SM1 sigma-or-None, SM1 key lanes, SM2 sigma-or-None, SM2 key lanes).

    "radix" packs both stages; "lax" packs neither; "bitonic" keeps the
    legacy behaviour (SM1 packing per `pack_keys`, SM2 raw characters)."""
    sm1_sigma = sigma if (impl == "radix"
                          or (impl == "bitonic" and pack_keys)) else None
    w1 = packed_width(v, -1, sigma) if sm1_sigma is not None else v
    sm2_sigma = sigma if impl == "radix" else None
    nk2 = packed_width(v, -1, sigma) if sm2_sigma is not None else v
    return sm1_sigma, w1, sm2_sigma, nk2


def suffix_array_bsp(
    x,
    mesh: Mesh,
    axis: str = "bsp",
    v: int = 3,
    schedule=accelerated_next_v,
    base_threshold: int | None = None,
    counters: BSPCounters = NULL_COUNTERS,
    pack_keys: bool = True,
    sort_impl: str = "auto",
    _n0: int | None = None,
) -> np.ndarray:
    """Distributed suffix array of x over a 1-D mesh. Returns np.int32[n].

    `sort_impl` selects the shard-local sort family inside both Algorithm-2
    psorts ("auto" → packed-key "radix"; see `repro.bsp.psort`)."""
    x = np.asarray(x)
    n = int(len(x))
    p = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    impl = resolve_bsp_sort_impl(sort_impl, pack_keys)
    if p == 1:
        # degenerate mesh: Algorithm 2's splitter machinery needs p ≥ 2;
        # a 1-processor BSP run IS the single-device algorithm.
        counters.superstep("base/gather", h=n, w=n * 4)
        return suffix_array_jax(
            x, v=max(v, 3), schedule=schedule,
            base_threshold=base_threshold or 256).astype(np.int32)
    n0 = _n0 or n
    if base_threshold is None:
        base_threshold = max(1024, n0 // p)
    holder = _MeshHolder(mesh)
    shard = NamedSharding(mesh, P(axis))

    def rec(x_np: np.ndarray, v: int) -> np.ndarray:
        n = len(x_np)
        if n <= max(base_threshold, 2 * p * v, 8):
            # paper: |X'| ≤ n/p → ship to one processor, solve sequentially.
            counters.superstep("base/gather", h=n, w=n * 4)
            return suffix_array_jax(x_np, v=3)
        v = int(min(max(v, 3), n))
        n_pv, n_loc, m_loc, m_tot, tabs = round_geometry(n, p, v)
        dsize = len(tabs.D)
        xp_np = np.full(n_pv, -1, dtype=np.int32)
        xp_np[:n] = x_np
        xg = jax.device_put(xp_np, shard)     # one slice per mesh device

        # quantized so the data-dependent max collapses onto O(log σ)
        # distinct static-arg values (same packed bit width, no retrace)
        sigma = quantize_sigma(int(x_np.max()) + 1)
        sm1_sigma, w1, sm2_sigma, nk2 = _sm_widths(v, sigma, impl, pack_keys)
        xprime, distinct, over = _sm1(
            xg, p=p, v=v, n_loc=n_loc, m_loc=m_loc, vkey=v, axis=axis,
            mesh_holder=holder, sigma=sm1_sigma)
        _round_cost("SM1", n_loc, m_loc, p, v, dsize, w1 + 2, counters)
        _check_overflow(over, "SM1")

        # saca-lint: allow[SCHED001] host-uniform by construction: `distinct`
        # is a fully-replicated stage output (per-shard flags gathered via
        # out_specs) and the single host driver ANDs it — every rank follows
        # the same branch, so the recursion depth is globally consistent.
        if bool(np.asarray(distinct).all()):
            sa_rank = xprime                                  # ranks are final
        else:
            v_next = schedule(v, dsize, m_tot)
            sa_sub = rec(np.asarray(xprime).reshape(-1), v_next)
            inv = np.empty(m_tot, dtype=np.int32)
            inv[sa_sub] = np.arange(m_tot, dtype=np.int32)
            sa_rank = jax.device_put(inv, shard)

        sa, over = _sm2(xg, sa_rank, p=p, v=v, n_loc=n_loc, m_loc=m_loc,
                        vkey=v, axis=axis, mesh_holder=holder, impl=impl,
                        sigma=sm2_sigma)
        _round_cost("SM2", n_loc, m_loc, p, v, dsize, 3 + nk2 + dsize,
                    counters)
        _check_overflow(over, "SM2")
        sa = np.asarray(sa).reshape(-1)
        return sa[sa < n]                                     # trim pads

    # top-level all-distinct shortcut (recursion base of Algorithm 3)
    if n <= max(base_threshold, 2 * p * 3, 8):
        counters.superstep("base/gather", h=n, w=n * 4)
        return suffix_array_jax(x, v=3).astype(np.int32)
    return rec(x.astype(np.int32), v).astype(np.int32)


# --------------------------------------------------------------------------
# analytic cost model (C4/C5 — "model only" mode)
# --------------------------------------------------------------------------
def estimate_costs(
    n: int,
    p: int,
    *,
    v: int = 3,
    schedule=accelerated_next_v,
    base_threshold: int | None = None,
    sort_impl: str = "auto",
    pack_keys: bool = True,
    sigma: int = 256,
    distinct_round: int | None = None,
) -> BSPCounters:
    """Replay `suffix_array_bsp`'s superstep schedule without executing it.

    Returns a `BSPCounters` holding the supersteps/communication/work a run
    would log on an input that never triggers the all-distinct recursion
    short-circuit (the worst case — e.g. an all-equal text, for which the
    replay is *exact*: same labels, same S). With `distinct_round=r` it
    replays instead a run whose r-th round (1-based) finds every sample
    window distinct and so recurses no deeper — what a high-entropy text
    does once the windows outgrow its repeats. `sigma` is the level-0
    alphabet bound; deeper levels use the dense-rank bound m_tot, so H/W
    are estimates while S and the label sequence are structural.

    The replay instantiates each level's difference-cover tables
    (`round_geometry`), so call it with realistic (n, p); for asymptotic
    round counting at astronomic sizes use the capped model in
    `benchmarks/supersteps.py`.
    """
    ct = BSPCounters()
    impl = resolve_bsp_sort_impl(sort_impl, pack_keys)
    n = int(n)
    if p == 1:
        ct.superstep("base/gather", h=n, w=n * 4)
        return ct
    if base_threshold is None:
        base_threshold = max(1024, n // p)
    if n <= max(base_threshold, 2 * p * 3, 8):
        ct.superstep("base/gather", h=n, w=n * 4)
        return ct

    def rec(nn: int, vv: int, sig: int, depth: int) -> None:
        if nn <= max(base_threshold, 2 * p * vv, 8):
            ct.superstep("base/gather", h=nn, w=nn * 4)
            return
        vv = int(min(max(vv, 3), nn))
        n_pv, n_loc, m_loc, m_tot, tabs = round_geometry(nn, p, vv)
        dsize = len(tabs.D)
        _, w1, _, nk2 = _sm_widths(vv, quantize_sigma(sig), impl, pack_keys)
        _round_cost("SM1", n_loc, m_loc, p, vv, dsize, w1 + 2, ct)
        if depth != distinct_round:
            rec(m_tot, schedule(vv, dsize, m_tot), m_tot, depth + 1)
        _round_cost("SM2", n_loc, m_loc, p, vv, dsize, 3 + nk2 + dsize, ct)

    rec(n, max(v, 3), sigma, 1)
    return ct
