"""Distributed-correctness tests (subprocess with forced host device counts):
the shard_map MoE must compute exactly what the single-device path computes,
and the multi-pod mesh must lower end to end."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow  # subprocess shard_map equivalence runs


def _run(code: str, timeout: int = 600):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_shardmap_matches_local_ep():
    """Expert-parallel shard_map MoE == single-device dispatch (4 experts
    over a 2-way model axis; batch over a 2-way data axis)."""
    out = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro import shardctx
from repro.models import moe as M
from repro.models.common import ModelConfig

cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=32,
                  num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=64,
                  num_experts=4, num_experts_per_tok=2,
                  moe_capacity_factor=2.0,
                  param_dtype="float32", compute_dtype="float32")
rng = jax.random.PRNGKey(0)
p = M.moe_init(rng, cfg)
x = jax.random.normal(rng, (4, 8, 32))
y_local, aux_local = M.moe_apply(p, x, cfg)        # no mesh installed
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
with shardctx.use_mesh(mesh):
    y_sm, aux_sm = jax.jit(lambda p, x: M.moe_apply(p, x, cfg))(p, x)
np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_sm),
                           atol=1e-5, rtol=1e-5)
np.testing.assert_allclose(float(aux_local), float(aux_sm), atol=1e-5)
print("MOE_EP_OK")
""")
    assert "MOE_EP_OK" in out


def test_moe_shardmap_matches_local_tp_f():
    """ffn-TP fallback (experts don't divide the axis) == local dispatch."""
    out = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro import shardctx
from repro.models import moe as M
from repro.models.common import ModelConfig

cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=32,
                  num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=64,
                  num_experts=3, num_experts_per_tok=2,   # 3 % 2 != 0 -> TP-f
                  moe_capacity_factor=2.0,
                  param_dtype="float32", compute_dtype="float32")
rng = jax.random.PRNGKey(1)
p = M.moe_init(rng, cfg)
x = jax.random.normal(rng, (2, 8, 32))
y_local, _ = M.moe_apply(p, x, cfg)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
with shardctx.use_mesh(mesh):
    y_sm, _ = jax.jit(lambda p, x: M.moe_apply(p, x, cfg))(p, x)
np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_sm),
                           atol=1e-5, rtol=1e-5)
print("MOE_TPF_OK")
""")
    assert "MOE_TPF_OK" in out


def test_multipod_mesh_lowering():
    """The 3-axis ("pod","data","model") mesh lowers a train step (reduced
    device count 8 = (2,2,2))."""
    out = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.launch.dryrun import run_pair
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
rec = run_pair("deepseek-7b", "train_4k", multi_pod=True, out_dir="",
               verbose=False, mesh=mesh)
assert rec["axes"] == ["pod", "data", "model"]
assert rec["roofline"]["bound_time_s"] > 0
print("MULTIPOD_OK", rec["roofline"]["dominant"])
""")
    assert "MULTIPOD_OK" in out


def test_int8_dryrun_lowering():
    out = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.launch.dryrun import run_pair
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
rec = run_pair("mistral-nemo-12b", "decode_32k", multi_pod=False,
               out_dir="", verbose=False, mesh=mesh, int8=True)
assert rec["int8"] is True
print("INT8_OK")
""")
    assert "INT8_OK" in out


def test_train_on_local_mesh_matches_single_device():
    """2-device data-parallel training step == single-device step."""
    out = _run("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro import shardctx
from repro.configs.registry import ARCHS
from repro.launch.steps import make_train_step
from repro.models import api
from repro.train.optimizer import AdamW

cfg = ARCHS["deepseek-7b"].smoke
params = api.init_params(jax.random.PRNGKey(0), cfg)
opt = AdamW(learning_rate=1e-3)
batch = {"tokens": jnp.ones((4, 16), jnp.int32),
         "labels": jnp.ones((4, 16), jnp.int32)}
p1, _, m1 = jax.jit(make_train_step(cfg, opt))(params, opt.init(params), batch)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 1), ("data", "model"))
from repro.launch import sharding
pspecs = sharding.param_pspecs(api.abstract_params(cfg), cfg, mesh)
p_sh = sharding.to_named(pspecs, mesh)
with shardctx.use_mesh(mesh):
    step = jax.jit(make_train_step(cfg, opt, mesh=mesh))
    p2, _, m2 = step(params, opt.init(params), batch)
np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
d = jax.tree_util.tree_map(lambda a, b: float(jnp.max(jnp.abs(
    a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
assert max(jax.tree_util.tree_leaves(d)) < 1e-4
print("DP_TRAIN_OK")
""")
    assert "DP_TRAIN_OK" in out
