"""Sharding rules + a reduced-mesh dry-run integration test (subprocess, so
the forced device count never leaks into this test process)."""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import ARCHS
from repro.launch import sharding
from repro.launch.mesh import make_local_mesh
from repro.models import api

pytestmark = pytest.mark.slow  # subprocess dry-runs with forced device counts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pspec_by_path(tree):
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, P))[0]
    return {tuple(str(getattr(k, "key", k)) for k in path): spec
            for path, spec in flat}


def test_param_pspecs_tp_rules():
    cfg = ARCHS["deepseek-7b"].smoke
    mesh = make_local_mesh(1, 1)  # axis sizes 1 -> all replicated

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 4}
    abs_p = api.abstract_params(cfg)
    specs = _pspec_by_path(sharding.param_pspecs(abs_p, cfg, FakeMesh()))
    # layer weights are stacked: leading L dim
    assert specs[("layers", "attn", "wq", "w")] == P(None, None, "model")
    assert specs[("layers", "attn", "wo", "w")] == P(None, "model", None)
    assert specs[("layers", "mlp", "wi", "w")] == P(None, None, "model")
    assert specs[("layers", "mlp", "wd", "w")] == P(None, "model", None)
    assert specs[("layers", "ln1", "scale")] == P()
    assert specs[("embed", "embedding")] == P("model", None)


def test_param_pspecs_fsdp_adds_data_axis():
    cfg = ARCHS["deepseek-7b"].smoke

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 4}
    abs_p = api.abstract_params(cfg)
    specs = _pspec_by_path(
        sharding.param_pspecs(abs_p, cfg, FakeMesh(), fsdp=True))
    assert specs[("layers", "attn", "wq", "w")] == P(None, "data", "model")
    # stacked norm scales (L, d) are rank-2 -> ZeRO shards them too
    assert specs[("layers", "ln1", "scale")] == P(None, "data")
    # truly-1D leaves stay replicated
    assert specs[("final_norm", "scale")] == P()


def test_moe_expert_parallel_vs_tp_fallback():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 2, "model": 4}
    # 4 experts / 4-way axis -> EP on the expert dim
    cfg = ARCHS["qwen3-moe-235b-a22b"].smoke  # 4 experts in smoke
    specs = _pspec_by_path(sharding.param_pspecs(
        api.abstract_params(cfg), cfg, FakeMesh()))
    assert specs[("layers", "moe", "wi")] == P(None, "model", None, None)
    # granite full config: 40 experts don't divide 16 -> TP on ffn dim
    class Mesh16:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    gcfg = ARCHS["granite-moe-3b-a800m"].config
    gspecs = _pspec_by_path(sharding.param_pspecs(
        api.abstract_params(gcfg), gcfg, Mesh16()))
    assert gspecs[("layers", "moe", "wi")] == P(None, None, None, "model")
    assert gspecs[("layers", "moe", "wd")] == P(None, None, "model", None)


def test_cache_pspecs_batch_vs_seq_sharding():
    cfg = ARCHS["deepseek-7b"].config

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 4}
    cache = api.cache_spec(cfg, batch=8, seq=1024)
    specs = _pspec_by_path(sharding.cache_pspecs(cache, cfg, FakeMesh(), batch=8))
    assert specs[("k",)][1] == "data"          # batch sharded
    cache1 = api.cache_spec(cfg, batch=1, seq=1024)
    specs1 = _pspec_by_path(sharding.cache_pspecs(cache1, cfg, FakeMesh(), batch=1))
    assert specs1[("k",)][1] is None           # batch=1 -> seq sharded instead
    assert specs1[("k",)][2] == "data"


@pytest.mark.parametrize("arch,shape", [
    ("deepseek-7b", "decode_32k"),
    ("rwkv6-1.6b", "train_4k"),
    ("qwen3-moe-235b-a22b", "prefill_32k"),
])
def test_dryrun_reduced_mesh_subprocess(arch, shape, tmp_path):
    """lower().compile() succeeds on a (2,2) mesh with 4 host devices —
    the same code path the production dry-run uses at (16,16)/(2,16,16)."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, json
from repro.launch.dryrun import run_pair
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
rec = run_pair("{arch}", "{shape}", multi_pod=False, out_dir="", verbose=False,
               mesh=mesh)
assert rec["roofline"]["bound_time_s"] > 0
print("DRYRUN_OK", rec["roofline"]["dominant"])
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "DRYRUN_OK" in out.stdout, out.stderr[-2000:]


def test_production_dryrun_artifacts_complete():
    """The background production sweep must cover every supported pair on
    both meshes (skipped if artifacts were not generated yet)."""
    out_dir = os.path.join(REPO, "artifacts", "dryrun")
    if not os.path.isdir(out_dir):
        pytest.skip("no dry-run artifacts")
    from repro.configs.registry import pairs
    missing = []
    for aid, sid in pairs():
        for tag in ("single", "multi"):
            p = os.path.join(out_dir, f"{aid}__{sid}__{tag}.json")
            if not os.path.exists(p):
                missing.append((aid, sid, tag))
    assert not missing, f"missing dry-runs: {missing}"


# ------------------------------------------------- comms_summary (DESIGN §10)
@pytest.mark.parametrize("arch", ["deepseek-7b", "qwen1.5-110b"])
def test_comms_summary_matches_analytic_plan_subprocess(arch):
    """The cluster simulator's analytic comms model
    (``repro.core.distributed.plan_shards``) must stay within 10% of what
    GSPMD actually lowers for the decode step — ``comms_summary`` compiles
    the pair on a (1, 4) mesh and reports the per-shard link bytes.  (For
    the dense archs the analytic model is in fact exact: two f32
    activation all-reduces per layer + embedding, one logits all-gather.)
    """
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, json
from repro.launch.dryrun import comms_summary
from repro.launch.mesh import make_mesh
mesh = make_mesh((1, 4), ("data", "model"))
s = comms_summary("{arch}", "decode_32k", mesh=mesh)
print("COMMS_JSON", json.dumps(s))
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = next(l for l in out.stdout.splitlines()
                if l.startswith("COMMS_JSON "))
    s = json.loads(line[len("COMMS_JSON "):])

    # ---- stable schema (treat as API)
    for key in ("arch", "shape", "kind", "mesh", "axes", "model_parallel",
                "loop_trips", "counts", "per_kind", "per_shard_bytes",
                "total_bytes"):
        assert key in s, key
    assert s["arch"] == arch
    assert s["kind"] == "decode"
    assert s["model_parallel"] == 4
    assert s["per_shard_bytes"] > 0
    assert s["total_bytes"] == pytest.approx(4 * s["per_shard_bytes"])
    assert s["per_shard_bytes"] == pytest.approx(
        sum(s["per_kind"].values()))

    # ---- the 10% sim-vs-dryrun validation gate
    from repro.configs.base import SHAPES
    from repro.core.distributed import plan_shards
    batch = SHAPES["decode_32k"].global_batch
    plan = plan_shards(arch, 4, batch=batch)
    analytic = plan.step_bytes(batch)
    lowered = s["per_shard_bytes"]
    assert abs(analytic - lowered) / lowered < 0.10, (analytic, lowered)
