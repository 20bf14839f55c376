"""Compile one program for a described TPU v5e (no chip attached) and time it.

    python tools/tpu_compile_probe.py sort --operands 4 --rows 65536
    python tools/tpu_compile_probe.py lsd --lanes 1 --rows 41943042
    python tools/tpu_compile_probe.py sm1 [--n 33554496] [--p 4]
    python tools/tpu_compile_probe.py sm2 [--n 33554496] [--p 4]

`sort` is a multi-operand `lax.sort` of int32 operands, all of them keys
(the lax window sort before `repro.core.lsd_sort`); `lsd` is
`lsd_argsort` over uint32 key lanes; `sm1` / `sm2` are the level-0 BSP
stage programs (`repro.bsp.suffix_array._sm1` / `_sm2`, packed-key
"radix" sorts) of an n-character byte text over a p-chip v5e mesh. The
default n is the 2^25-character, 64-document smoke corpus with its
separators.

Prints one line: the program, its compile seconds on this host, and the
sort and scatter instructions left in the optimised HLO. Needs the TPU
compiler (libtpu); run one probe per process, since two processes cannot
load it at once. The persistent compilation cache is not used.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lower_program(topo, what: str, *, rows: int = 1 << 16,
                  operands: int = 1, lanes: int = 1,
                  n: int = (1 << 25) + 64, p: int = 4):
    """(label, jax Lowered) of one probe program for the described
    topology `topo` (see the module docstring for `what`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    one = SingleDeviceSharding(topo.devices[0])
    if what == "sort":
        def fn(*ops):
            return jax.lax.sort(ops, num_keys=len(ops))
        shapes = [jax.ShapeDtypeStruct((rows,), jnp.int32,
                                       sharding=one)] * operands
        return (f"lax.sort {operands} operands x {rows} rows",
                jax.jit(fn).lower(*shapes))
    if what == "lsd":
        from repro.core.lsd_sort import lsd_argsort
        spec = jax.ShapeDtypeStruct((lanes, rows), jnp.uint32, sharding=one)
        return (f"lsd_argsort {lanes} lanes x {rows} rows",
                jax.jit(lambda x: lsd_argsort(x, [32] * lanes)).lower(spec))
    from repro.bsp import suffix_array as bsa
    from repro.bsp.psort import quantize_sigma
    v = 3
    mesh = Mesh(np.array(topo.devices[:p]).reshape(p), ("bsp",))
    n_pv, n_loc, m_loc, m_tot, _ = bsa.round_geometry(n, p, v)
    # the facade shifts byte values above the 64 document separators
    s1, _, s2, _ = bsa._sm_widths(v, quantize_sigma(256 + 64), "radix", True)
    shard = NamedSharding(mesh, P("bsp"))
    xg = jax.ShapeDtypeStruct((n_pv,), jnp.int32, sharding=shard)
    kw = dict(p=p, v=v, n_loc=n_loc, m_loc=m_loc, vkey=v, axis="bsp",
              mesh_holder=bsa._MeshHolder(mesh))
    label = f"{what} n={n} p={p} (level 0, v=3)"
    if what == "sm1":
        return label, bsa._sm1.lower(xg, sigma=s1, **kw)
    ranks = jax.ShapeDtypeStruct((m_tot,), jnp.int32, sharding=shard)
    return label, bsa._sm2.lower(xg, ranks, impl="radix", sigma=s2, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("sort", "lsd", "sm1", "sm2"))
    ap.add_argument("--rows", type=int, default=1 << 16)
    ap.add_argument("--operands", type=int, default=1)
    ap.add_argument("--lanes", type=int, default=1)
    ap.add_argument("--n", type=int, default=(1 << 25) + 64)
    ap.add_argument("--p", type=int, default=4)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    label, lowered = lower_program(
        topo, args.what, rows=args.rows, operands=args.operands,
        lanes=args.lanes, n=args.n, p=args.p)
    t0 = time.perf_counter()
    hlo = lowered.compile().as_text()
    secs = time.perf_counter() - t0
    print(f"{label}: compiled for a described v5e in {secs:.1f}s; "
          f"{hlo.count(' sort(')} sort, {hlo.count(' scatter(')} scatter "
          f"instructions", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
