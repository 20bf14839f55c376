import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines — jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, record memory_analysis / cost_analysis / collective
bytes for §Dry-run and §Roofline.

Usage:
    python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, model_archs
from ..models.config import SHAPES
from ..models.layers import logits_from_embedding
from ..models.lm import decode_step, forward_hidden, encode
from ..models.sharding import ShardingRules
from ..train.train_step import make_train_step
from .mesh import make_production_mesh, mesh_num_devices
from .specs import (abstract_train_state, cell_runs, decode_batch_specs,
                    decode_state_specs, default_train_config, dp_axes,
                    prefill_specs, train_batch_specs, train_state_shardings)

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")

_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?\s*([a-z0-9]+)\[([\d,]*)\]")
_OPS_RE = re.compile(r"\(([^)]*)\)")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum operand sizes of every collective op in the optimised HLO."""
    sizes: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if m:
            name, dt, dims = m.groups()
            if dt in DTYPE_BYTES:
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                sizes[name] = n * DTYPE_BYTES[dt]
    out = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for c in COLLECTIVES:
            token = f" {c}(" if not stripped.startswith(c) else f"{c}("
            if f"= {c}(" in stripped or f" {c}(" in stripped:
                if f"{c}(" not in stripped:
                    continue
                counts[c] += 1
                ops_m = _OPS_RE.search(stripped[stripped.index(f"{c}("):])
                total = 0
                if ops_m:
                    for op in ops_m.group(1).split(","):
                        op = op.strip().lstrip("%")
                        total += sizes.get(op, 0)
                if total == 0:
                    m = _DEF_RE.match(line)
                    if m and m.group(2) in DTYPE_BYTES:
                        n = 1
                        for d in m.group(3).split(","):
                            if d:
                                n *= int(d)
                        total = n * DTYPE_BYTES[m.group(2)]
                out[c] += total
                break
    out["_counts"] = counts
    return out


def model_params_breakdown(cfg):
    """(n_total, n_active, n_embed) from the actual abstract param tree.
    MoE expert params are counted at top_k/E for n_active."""
    from .specs import abstract_params
    params, _ = abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    total = active = emb = 0
    for path, leaf in flat:
        n = int(np.prod(leaf.shape))
        keys = [getattr(p, "key", str(p)) for p in path]
        total += n
        if "embed" in keys:
            emb += n
            continue
        if "moe" in keys and "router" not in keys:
            active += n * cfg.top_k / max(cfg.n_experts, 1)
        else:
            active += n
    return total, active, emb


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (prefill/decode);
    N excludes the embedding table (the HLO/model ratio row captures
    attention-score and lm-head compute)."""
    _, n_active, _ = model_params_breakdown(cfg)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch   # one token per sequence


def build_cell(arch: str, shape_name: str, mesh, rules=None):
    """Returns (fn, example_args) ready for jit(...).lower(*args)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rules = rules or ShardingRules()

    if shape.kind == "train":
        cfg = cfg.replace(remat="full")     # per-layer remat (§Perf iter. 6)
        tcfg = default_train_config(cfg)
        state_abs, axes = abstract_train_state(cfg, tcfg)
        st_sh = train_state_shardings(cfg, tcfg, state_abs, axes, mesh, rules)
        state_abs = jax.tree_util.tree_map(
            lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh),
            state_abs, st_sh)
        batch = train_batch_specs(cfg, shape, mesh)
        step = make_train_step(cfg, tcfg, mesh=mesh)
        return step, (state_abs, batch)

    from .specs import abstract_params, params_shardings
    params_abs, axes = abstract_params(cfg)
    p_sh = params_shardings(axes, mesh, rules, params_abs)
    params_abs = jax.tree_util.tree_map(
        lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh),
        params_abs, p_sh)

    if shape.kind == "prefill":
        batch = prefill_specs(cfg, shape, mesh)

        def prefill(params, batch):
            enc_out = None
            if cfg.is_encdec:
                enc_out = encode(params, cfg, batch["enc_embeds"], mesh=mesh)
            hidden, _, _ = forward_hidden(
                params, cfg, tokens=batch["tokens"], enc_out=enc_out,
                mesh=mesh)
            return logits_from_embedding(
                hidden[:, -1:], params["embed"], cap=cfg.logit_softcap)

        return prefill, (params_abs, batch)

    # decode
    states_abs, _ = decode_state_specs(cfg, shape, mesh)
    dbatch = decode_batch_specs(cfg, shape, mesh)

    def serve_step(params, states, token, cur_pos, *rest):
        enc_out = rest[0] if rest else None
        return decode_step(params, cfg, token, states, cur_pos,
                           enc_out=enc_out, mesh=mesh)

    args = [params_abs, states_abs, dbatch["token"], dbatch["cur_pos"]]
    if cfg.is_encdec:
        args.append(dbatch["enc_out"])
    return serve_step, tuple(args)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             rules=None, tag: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "chips": mesh_num_devices(mesh), "tag": tag}
    t0 = time.time()
    try:
        fn, args = build_cell(arch, shape_name, mesh, rules)
        with mesh:
            lowered = jax.jit(fn).lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            hlo = compiled.as_text()
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        rec["flops"] = float(cost.get("flops", -1)) if cost else -1.0
        rec["bytes"] = float(cost.get("bytes accessed", -1)) if cost else -1.0
        if mem is not None:
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    rec[k] = int(v)
        rec["collectives"] = parse_collective_bytes(hlo)
        # loop-corrected totals (cost_analysis counts while bodies once;
        # hlo_stats multiplies by known_trip_count — see hlo_stats.py)
        from .hlo_stats import hlo_stats
        st = hlo_stats(hlo)
        rec["flops_corrected"] = float(st["flops"])
        rec["bytes_corrected"] = float(st["bytes"])
        rec["collective_bytes_corrected"] = float(st["collective_bytes"])
        rec["collectives_corrected"] = {
            k: float(v) for k, v in st.items()
            if k not in ("flops", "bytes", "collective_bytes")}
        rec["hlo_lines"] = hlo.count("\n")
        rec["model_flops"] = model_flops_estimate(cfg, shape)
        rec["status"] = "ok"
    except Exception as e:  # record failures — they are bugs to fix
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_kind}{tag}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s) for a in model_archs()
                 for s in SHAPES if cell_runs(get_config(a), s)]
    else:
        cells = [(args.arch, args.shape)]

    for arch, shape_name in cells:
        for mk in meshes:
            fname = os.path.join(args.out,
                                 f"{arch}__{shape_name}__{mk}.json")
            if args.skip_existing and os.path.exists(fname):
                print(f"skip {arch} {shape_name} {mk}")
                continue
            rec = run_cell(arch, shape_name, mk, args.out)
            ok = rec["status"]
            print(f"{arch:22s} {shape_name:12s} {mk:6s} {ok:5s} "
                  f"compile={rec.get('compile_s', '-'):>7}s "
                  f"flops={rec.get('flops', -1):.3e} "
                  f"err={rec.get('error', '')[:90]}",
                  flush=True)


if __name__ == "__main__":
    main()
