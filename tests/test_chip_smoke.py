"""chip_smoke.py rehearsed on the CPU: its phases at small sizes (Pallas in
interpret mode, four virtual devices for the tensor-parallel phase), and its
refusal to run anywhere but on a TPU."""
import os
import shutil
import subprocess
import sys

from repro.configs import deepseek_7b

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SMALL_KERNELS = {
    "flash_attention": dict(b=1, s=256, h=4, kh=2, hd=64),
    "flash_decode": dict(b=2, s=512, h=4, kh=2, hd=64),
    "wkv6": dict(b=1, t=128, h=2, hd=32),
}


def _run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_tpu():
    out = _run_script(REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_refuses_without_the_repository(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run_script(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_kernel_phase_interpret():
    errs = chip_smoke.kernel_phase(0, SMALL_KERNELS, interpret=True)
    assert set(errs) == {"flash_attention", "flash_decode", "wkv6_out",
                         "wkv6_state"}


def test_server_phase_smoke():
    cfg = deepseek_7b.SMOKE
    res = chip_smoke.server_phase(cfg, slots=2, max_seq=64, seed=0,
                                  n_requests=4, prompt_range=(5, 30),
                                  n_new=6)
    assert res["tokens_out"] == 24
    assert res["agreement"]["positions"] == 24     # every served token
    assert res["agreement"]["max_diff"] < 1e-4     # float32 smoke config


def test_four_chip_phase_on_virtual_devices():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import chip_smoke
from repro.configs import mistral_nemo_12b
res = chip_smoke.four_chip_phase(jax.devices()[:4], 0,
                                 full_cfg=mistral_nemo_12b.SMOKE,
                                 cut_layers=1, batch=2, prompt_len=8, n_new=4)
assert len(res["bytes_per_device"]) == 4
assert res["served_vs_forward"]["positions"] == 8
assert res["tp_vs_one_forward"]["positions"] == 8
assert 2 <= res["tp_vs_one_served"]["positions"] <= 8
print("FOUR_OK", res["tp_vs_one_served"]["max_diff"])
"""
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(REPO, "src"), REPO]))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_OK" in out.stdout
