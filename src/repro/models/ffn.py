"""Feed-forward layers: SwiGLU dense MLP and sort-free capacity-based MoE
with true expert parallelism (single symmetric all_to_all pair over the
`model` mesh axis, DeepSeek/Switch-style capacity-factor semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import PartitionSpec as P

from ..bsp.primitives import within_group_index
from .layers import COMPUTE_DTYPE, activation


# --------------------------------------------------------------------------
# dense SwiGLU
# --------------------------------------------------------------------------
def init_mlp(col, prefix: str, cfg):
    col.param(f"{prefix}.wg", (cfg.d_model, cfg.d_ff), ("embed_fsdp", "mlp"))
    col.param(f"{prefix}.wu", (cfg.d_model, cfg.d_ff), ("embed_fsdp", "mlp"))
    col.param(f"{prefix}.wd", (cfg.d_ff, cfg.d_model),
              ("mlp", "embed_fsdp"),
              scale=0.02 / np.sqrt(2 * cfg.n_layers))


def _pin(t, mesh, spec_builder):
    if mesh is None:
        return t
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = spec_builder(mesh)
    if spec is None:
        return t
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))


def _ffn_spec(t_shape, ff: int):
    """[B, S, ff] → (dp, None, model-if-divisible): forbids partial-sum
    outputs, so XLA resolves the FSDP contraction by all-gathering the
    (small) weight shard instead of all-reducing the (huge) activation
    (§Perf iteration 8)."""
    def build(mesh):
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        msz = mesh.shape.get("model", 1)
        dpsz = 1
        for a in dp:
            dpsz *= mesh.shape[a]
        b_ok = dp and t_shape[0] % dpsz == 0
        f_ok = "model" in mesh.axis_names and ff % msz == 0
        if not (b_ok or f_ok):
            return None
        return P(dp if b_ok else None, None, "model" if f_ok else None)
    return build


def mlp_layer(p, cfg, x, mesh=None):
    act = activation(cfg.act)
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype),
                   preferred_element_type=COMPUTE_DTYPE)
    g = _pin(g, mesh, _ffn_spec(g.shape, cfg.d_ff))
    u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(x.dtype),
                   preferred_element_type=COMPUTE_DTYPE)
    u = _pin(u, mesh, _ffn_spec(u.shape, cfg.d_ff))
    h = (act(g) * u).astype(COMPUTE_DTYPE)
    return jnp.einsum("bsf,fd->bsd", h, p["wd"].astype(h.dtype),
                      preferred_element_type=COMPUTE_DTYPE)


# --------------------------------------------------------------------------
# MoE (expert parallel)
# --------------------------------------------------------------------------
def init_moe(col, prefix: str, cfg):
    E = cfg.n_experts
    col.param(f"{prefix}.router", (cfg.d_model, E), ("embed", None))
    col.param(f"{prefix}.wg", (E, cfg.d_model, cfg.d_ff),
              ("experts", "embed_fsdp", "expert_mlp"))
    col.param(f"{prefix}.wu", (E, cfg.d_model, cfg.d_ff),
              ("experts", "embed_fsdp", "expert_mlp"))
    col.param(f"{prefix}.wd", (E, cfg.d_ff, cfg.d_model),
              ("experts", "expert_mlp", "embed_fsdp"),
              scale=0.02 / np.sqrt(2 * cfg.n_layers))


def _moe_local(x, wr, wg, wu, wd, *, cfg, tp: int, axis: str | None):
    """Local-shard MoE body. x [T, d]; wg/wu/wd [E_loc, d, ff]/[E_loc, ff, d].

    When axis is None (single shard / smoke) tp == 1 and no collectives run.
    Returns ([T, d], aux_loss)."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // tp
    act = activation(cfg.act)

    logits = jnp.einsum("td,de->te", x, wr.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)                    # [T, k]
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch): E · Σ_e f_e · P_e
    me_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=1), axis=0)
    pr_frac = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(me_frac * pr_frac)

    ids_f = ids.reshape(-1)                                # [T*k]
    gate_f = gate.reshape(-1)
    src_f = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    owner = ids_f // E_loc
    valid = jnp.ones_like(ids_f, dtype=bool)
    cap = int(cfg.capacity_factor * T * k / tp) + 8
    slot = within_group_index(owner, valid, tp)
    keep = slot < cap

    tok_buf = jnp.zeros((tp, cap, d), COMPUTE_DTYPE)
    meta_buf = jnp.full((tp, cap, 1), -1, jnp.int32)
    ow = jnp.where(keep, owner, tp)
    tok_buf = tok_buf.at[ow, slot].set(
        x.astype(COMPUTE_DTYPE)[src_f], mode="drop")
    meta_buf = meta_buf.at[ow, slot, 0].set(ids_f % E_loc, mode="drop")

    if axis is not None:
        tok_buf = jax.lax.all_to_all(tok_buf, axis, 0, 0, tiled=False)
        meta_buf = jax.lax.all_to_all(meta_buf, axis, 0, 0, tiled=False)

    R = tp * cap
    toks = tok_buf.reshape(R, d)
    eid = meta_buf.reshape(R)
    ev = eid >= 0
    cap_e = int(cfg.capacity_factor * T * k * tp / E) + 8
    eslot = within_group_index(eid, ev, E_loc)
    ekeep = ev & (eslot < cap_e)
    e_ix = jnp.where(ekeep, eid, E_loc)
    ebuf = jnp.zeros((E_loc, cap_e, d), COMPUTE_DTYPE)
    ebuf = ebuf.at[e_ix, eslot].set(toks, mode="drop")
    rmap = jnp.full((E_loc, cap_e), -1, jnp.int32)
    rmap = rmap.at[e_ix, eslot].set(jnp.arange(R, dtype=jnp.int32),
                                    mode="drop")

    g = jnp.einsum("ecd,edf->ecf", ebuf, wg.astype(ebuf.dtype),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", ebuf, wu.astype(ebuf.dtype),
                   preferred_element_type=jnp.float32)
    h = (act(g) * u).astype(COMPUTE_DTYPE)
    y = jnp.einsum("ecf,efd->ecd", h, wd.astype(h.dtype),
                   preferred_element_type=jnp.float32).astype(COMPUTE_DTYPE)

    # symmetric return path: place results back in arrival slots, a2a back
    y_flat = jnp.zeros((R, d), COMPUTE_DTYPE)
    rix = jnp.where(rmap >= 0, rmap, R).reshape(-1)
    y_flat = y_flat.at[rix].set(y.reshape(-1, d), mode="drop")
    y_buf = y_flat.reshape(tp, cap, d)
    if axis is not None:
        y_buf = jax.lax.all_to_all(y_buf, axis, 0, 0, tiled=False)

    got = y_buf[ow.clip(0, tp - 1), slot]                  # [T*k, d]
    got = jnp.where((keep & valid)[:, None], got, 0)
    out = jnp.zeros((T, d), jnp.float32).at[src_f].add(
        got.astype(jnp.float32) * gate_f[:, None])
    return out.astype(COMPUTE_DTYPE), aux


def _moe_decode_local(x, wr, wg, wu, wd, *, cfg, tp: int, axis: str | None):
    """Replicated-token, expert-sliced MoE for small-S decode: every shard
    routes all T tokens, computes only its local experts' contributions, and
    the partial outputs are psum'd over the expert axis. No all_to_all."""
    T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // tp
    act = activation(cfg.act)
    me = jax.lax.axis_index(axis) if axis is not None else 0

    logits = jnp.einsum("td,de->te", x, wr.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    ids_f = ids.reshape(-1)
    gate_f = gate.reshape(-1)
    src_f = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    mine = (ids_f // E_loc) == me
    eid = jnp.where(mine, ids_f % E_loc, E_loc)
    cap_e = max(8, int(cfg.capacity_factor * T * k / max(E_loc, 1)) + 8)
    eslot = within_group_index(eid, mine, E_loc)
    keep = mine & (eslot < cap_e)
    e_ix = jnp.where(keep, eid, E_loc)
    ebuf = jnp.zeros((E_loc, cap_e, d), COMPUTE_DTYPE)
    ebuf = ebuf.at[e_ix, eslot].set(x.astype(COMPUTE_DTYPE)[src_f],
                                    mode="drop")
    g = jnp.einsum("ecd,edf->ecf", ebuf, wg.astype(ebuf.dtype),
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", ebuf, wu.astype(ebuf.dtype),
                   preferred_element_type=jnp.float32)
    h = (act(g) * u).astype(COMPUTE_DTYPE)
    y = jnp.einsum("ecf,efd->ecd", h, wd.astype(h.dtype),
                   preferred_element_type=jnp.float32)

    got = y[e_ix.clip(0, E_loc - 1), eslot]
    got = jnp.where(keep[:, None], got, 0)
    out = jnp.zeros((T, d), jnp.float32).at[src_f].add(
        got.astype(jnp.float32) * gate_f[:, None])
    if axis is not None:
        out = jax.lax.psum(out, axis)
    return out.astype(COMPUTE_DTYPE), jnp.float32(0.0)


def moe_layer(p, cfg, x, *, mesh=None, dp_axes=("pod", "data"),
              tp_axis: str = "model"):
    """x [B, S, d] (global). Uses shard_map EP when a mesh with tp_axis of
    size > 1 is provided; otherwise runs the single-shard body."""
    B, S, d = x.shape
    if mesh is None or tp_axis not in mesh.axis_names or \
            mesh.shape[tp_axis] == 1:
        out, aux = _moe_local(
            x.reshape(B * S, d), p["router"], p["wg"], p["wu"], p["wd"],
            cfg=cfg, tp=1, axis=None)
        return out.reshape(B, S, d), aux

    tp = mesh.shape[tp_axis]
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    dp_spec = dp if dp else None
    decode_path = (S % tp) != 0            # S too small to sequence-shard

    def body(x_blk, wr, wg, wu, wd):
        Bl, Sl, _ = x_blk.shape
        fn = _moe_decode_local if decode_path else _moe_local
        out, aux = fn(x_blk.reshape(Bl * Sl, d), wr, wg, wu, wd,
                      cfg=cfg, tp=tp, axis=tp_axis)
        # aux is per-shard; average over the whole mesh
        aux = jax.lax.pmean(aux, tp_axis)
        for a in dp:
            aux = jax.lax.pmean(aux, a)
        return out.reshape(Bl, Sl, d), aux[None]

    x_seq_spec = None if decode_path else tp_axis
    out, aux = shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, x_seq_spec, None), P(), P(tp_axis, None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None)),
        out_specs=(P(dp_spec, x_seq_spec, None), P(None)),
    )(x, p["router"], p["wg"], p["wu"], p["wd"])
    return out, aux[0]
