"""The shipped examples must keep running (fast variants)."""
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # Force the CPU platform: with an unset JAX_PLATFORMS a libtpu install
    # without TPU hardware spends minutes in init retry backoff.
    env["JAX_PLATFORMS"] = "cpu"
    # the CLIs turn on the persistent compilation cache; tests must not
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    # Redirect to files rather than capture_output pipes: on some sandboxed
    # kernels a jax child writing to a pipe runs an order of magnitude
    # slower than one writing to a file.
    with tempfile.TemporaryFile("w+") as fo, \
            tempfile.TemporaryFile("w+") as fe:
        p = subprocess.run([sys.executable] + args, stdout=fo, stderr=fe,
                           text=True, timeout=timeout, env=env, cwd=ROOT)
        fo.seek(0)
        fe.seek(0)
        p.stdout, p.stderr = fo.read(), fe.read()
    return p


def test_auction_recruitment_example():
    p = run(["examples/auction_recruitment.py"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "MMFL Max-Min Fair" in p.stdout


def test_train_concurrent_lms_example_short():
    p = run(["examples/train_concurrent_lms.py", "--rounds", "2",
             "--archs", "smollm-135m,qwen1.5-0.5b"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "final losses" in p.stdout


def test_serve_launcher_short():
    p = run(["-m", "repro.launch.serve", "--arch", "smollm-135m",
             "--preset", "tiny", "--batch", "2", "--prompt-len", "8",
             "--gen", "4"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "decoded" in p.stdout


def test_train_async_mmfl_example_short():
    p = run(["examples/train_async_mmfl.py", "--arrivals", "60",
             "--clients", "10", "--tasks", "synth-mnist,synth-fmnist"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "async final accs" in p.stdout
    assert "straggler barrier" in p.stdout


def test_launch_train_async_mode():
    """--async on the production launcher: event engine drives the arch
    train tasks end-to-end."""
    p = run(["-m", "repro.launch.train", "--archs", "smollm-135m",
             "--async", "--arrivals", "9", "--clients", "6",
             "--buffer", "3", "--seq", "32", "--batch", "4"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "final losses" in p.stdout


def test_true_fedavg_tau_local_steps():
    """tau>1 path: vmapped local SGD + Pallas fedavg aggregation."""
    p = run(["-m", "repro.launch.train", "--archs", "smollm-135m",
             "--rounds", "2", "--clients", "6", "--seq", "32",
             "--batch", "4", "--tau", "2"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "final losses" in p.stdout
