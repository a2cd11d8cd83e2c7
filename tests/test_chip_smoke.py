"""chip_smoke.py: refuses to run without a TPU, and its phases and checks
hold at the tiny preset on the CPU (the chip runs them at full width)."""
import os
import shutil
import subprocess
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def run(args, cwd, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
               **env_extra)
    env.pop("PYTHONPATH", None)
    # files, not pipes: see tests/test_examples.py
    with tempfile.TemporaryFile("w+") as fo, tempfile.TemporaryFile("w+") as fe:
        p = subprocess.run([sys.executable] + args, stdout=fo, stderr=fe, text=True,
                           timeout=600, env=env, cwd=cwd)
        fo.seek(0)
        fe.seek(0)
        p.stdout, p.stderr = fo.read(), fe.read()
    return p


def test_cpu_only_exits_nonzero_before_any_phase():
    p = run([os.path.join(ROOT, "chip_smoke.py")], ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = run(["chip_smoke.py"], str(tmp_path))
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


@pytest.mark.parametrize("phase", ["phase_sync", "phase_fedavg", "phase_async"])
def test_phase_passes_its_checks_at_tiny_preset(phase):
    getattr(chip_smoke, phase)("tiny", "cpu")


def test_fedavg_kernel_matches_jnp_at_tiny_preset():
    chip_smoke.check_fedavg_kernel_matches_jnp("tiny")


def test_check_result_rejects_a_non_finite_loss():
    import numpy as np

    class Result:
        task_names = [chip_smoke.SMOLLM]
        loss = np.array([[6.2], [np.nan]])
        params = []

    with pytest.raises(AssertionError, match="not all finite"):
        chip_smoke.check_result("t", Result, "tiny", "cpu")


def test_four_chip_path_on_four_virtual_devices():
    prog = ("import chip_smoke, jax; assert jax.device_count() == 4; "
            "chip_smoke.four_chips('tiny', 'cpu'); print('FOUR_OK')")
    p = run(["-c", prog], ROOT,
            XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "FOUR_OK" in p.stdout
    assert "collectives ['all-reduce']" in p.stdout
