"""Launch-layer tests: spec construction for every cell, HLO collective
parser, and a true (tiny-mesh) lowering in a subprocess."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_config, model_archs
from repro.models.config import SHAPES

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                   "src"))
RESULTS = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                       "results", "dryrun"))


def test_cell_applicability_table():
    from repro.launch.specs import cell_runs
    runs = sum(cell_runs(get_config(a), s)
               for a in model_archs() for s in SHAPES)
    assert runs == 35          # 40 − 5 documented long_500k skips


def test_parse_collective_bytes():
    from repro.launch.dryrun import parse_collective_bytes
    hlo = """
  %p0 = bf16[1024,512]{1,0} parameter(0)
  %ag = bf16[16384,512]{1,0} all-gather(%p0), dimensions={0}
  %ar = bf16[1024,512]{1,0} all-reduce(%p0), to_apply=%add
  %cp = bf16[1024,512]{1,0} collective-permute(%p0), source_target_pairs={{0,1}}
"""
    out = parse_collective_bytes(hlo)
    assert out["all-gather"] == 1024 * 512 * 2
    assert out["all-reduce"] == 1024 * 512 * 2
    assert out["collective-permute"] == 1024 * 512 * 2
    assert out["_counts"]["all-gather"] == 1


@pytest.mark.parametrize("arch", model_archs())
def test_model_flops_estimate_positive(arch):
    from repro.launch.dryrun import model_flops_estimate, \
        model_params_breakdown
    cfg = get_config(arch)
    total, active, emb = model_params_breakdown(cfg)
    assert total > active > 0 and emb > 0
    if cfg.is_moe:
        assert active < 0.6 * total
    for s in SHAPES.values():
        assert model_flops_estimate(cfg, s) > 0


def test_tiny_mesh_lowering_subprocess():
    """True .lower().compile() on an 8-device (2×4) mesh for a reduced arch
    — the fast CI version of the 512-device dry-run."""
    code = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.configs import get_config
    from repro.models.lm import lm_init
    from repro.train.train_step import TrainConfig, make_train_state, \\
        make_train_step
    from repro.train.optim import OptConfig
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((2, 4), ("data", "model"))
    cfg = get_config("gemma3_1b").smoke().replace(n_layers=6)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    tcfg = TrainConfig(opt=OptConfig())
    state = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
            sharding=NamedSharding(mesh, P())),
        jax.eval_shape(lambda: make_train_state(params, tcfg)))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 33), jax.numpy.int32,
             sharding=NamedSharding(mesh, P("data", None)))}
    step = make_train_step(cfg, tcfg, mesh=mesh)
    with mesh:
        compiled = jax.jit(step).lower(state, batch).compile()
    cost = compiled.cost_analysis()
    assert cost.get("flops", 0) > 0
    print("LOWER_OK", int(cost["flops"]))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=420)
    assert "LOWER_OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.skipif(not os.path.isdir(RESULTS),
                    reason="full dry-run results not present")
def test_full_dryrun_results_all_ok():
    """Once the 512-device sweep has run, every recorded cell must be ok."""
    recs = []
    for f in os.listdir(RESULTS):
        if f.endswith(".json"):
            recs.append(json.load(open(os.path.join(RESULTS, f))))
    assert recs, "no dry-run records"
    bad = [(r["arch"], r["shape"], r["mesh"], r.get("error", ""))
           for r in recs if r["status"] != "ok"]
    assert not bad, bad
