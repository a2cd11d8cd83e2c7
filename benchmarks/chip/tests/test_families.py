"""Model families (``families/<name>.py``, found by the configuration
file's ``family`` key): the dense family gives the numbers the harness
gave before families existed, a family added as new files alone is taken
by a whole run, and an unknown family is refused.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests/test_families.py -q
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]

import counts  # noqa: E402
import reference  # noqa: E402
from test_harness import tiny  # noqa: E402

# recorded before the dense decoder moved into families/dense.py
RECORDED = json.loads((HERE / "tests" / "data" / "dense_tiny.json").read_text())
CELL_OF = {"smollm-135m": "smollm-135m.sync-fedavg", "qwen3-0.6b": "qwen3-0.6b.sync-fused"}
SEED = 4_294_967_311


@pytest.mark.parametrize("config", sorted(RECORDED["configs"]))
def test_dense_family_gives_the_recorded_numbers(config):
    """The same seed gives bit-identical weights, reference loss and
    gradient norms on two weighted rows, and the same counts."""
    want = RECORDED["configs"][config]
    _, _, cfg, _ = tiny(CELL_OF[config])
    assert "family" not in cfg and reference.family(cfg).__name__ == "family_dense"
    params = reference.make_weights_fn(cfg)(reference.seed_key(RECORDED["seed"]))
    got = {jax.tree_util.keystr(p): hashlib.sha256(np.asarray(x).tobytes()).hexdigest()
           for p, x in jax.tree.flatten_with_path(params)[0]}
    assert got == want["weights"]
    tokens = np.random.default_rng(RECORDED["seed"]).integers(
        0, cfg["vocab_size"], (2, RECORDED["seq"])).astype(np.int32)
    loss, g = reference.Reference(cfg).value_and_grad(params, tokens, np.array([1.0, 0.5]))
    assert float(loss) == want["loss"]
    assert [float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
            for x in jax.tree.leaves(g)] == want["grad_norms"]
    published = json.loads((HERE / "configs" / f"{config}.json").read_text())
    for label, c in (("tiny", cfg), ("published", published)):
        assert {"matmul_params": counts.matmul_params(c),
                "train_flops_per_token": counts.train_flops_per_token(c, 256),
                "trained_params": counts.trained_params(c, reference.padded_vocab(c["vocab_size"]))
                } == want["counts"][label], label


def test_smollm_counts_by_hand():
    cfg = json.loads((HERE / "configs" / "smollm-135m.json").read_text())
    dense = reference.family(cfg)
    assert dense.matmul_params(cfg) == 30 * (576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536) + \
        576 * 49152 == 134_479_872
    assert dense.trained_params(cfg, 49152) == 134_515_008


# ------------------------------------------------------------ new files

QK_FAMILY = '''"""The dense family under another name, with qk-norm on whatever the
configuration file says."""
import reference

_dense = reference.family({})
GAINS, TINY = _dense.GAINS, _dense.TINY


def _qk(cfg):
    return dict(cfg, qk_norm=True)


def model_config(cfg, name):
    return _dense.model_config(_qk(cfg), name)


def weight_shapes(cfg):
    return _dense.weight_shapes(_qk(cfg))


def init_std(path, cfg):
    return _dense.init_std(path, _qk(cfg))


def loss_fn(params, cfg, tokens, row_w=None):
    return _dense.loss_fn(params, _qk(cfg), tokens, row_w)


def matmul_params(cfg):
    return _dense.matmul_params(_qk(cfg))


def train_flops_per_token(cfg, seq):
    return _dense.train_flops_per_token(_qk(cfg), seq)


def trained_params(cfg, padded_vocab):
    return _dense.trained_params(_qk(cfg), padded_vocab)
'''

# load the cell and run it whole at its own (tiny) size, in a process
# whose harness is the copy's, as a run in a later checkout would be
WHOLE_RUN = '''
import json, sys, types
root, src, workload = sys.argv[1:4]
sys.path[:0] = [root + "/benchmarks/chip", root + "/benchmarks/chip/metrics", src]
import jax, reference, run
manifest, entry, cfg, tr = run.load_cell(workload, run.ROOT)
args = types.SimpleNamespace(workload=workload, seed=int(sys.argv[4]), seconds=0.5, trace=0)
out = run.run_cell(args, manifest, entry, cfg, tr, jax, jax.devices())
out["family_file"] = reference.family(cfg).__file__
out["shapes"] = reference.family(cfg).weight_shapes(cfg)
print(json.dumps(out))
'''


def _checkout(tmp_path) -> Path:
    """A copy of the harness and the manifest, as a checkout holds them."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    return root


def _add_cell(root, config: str, cfg: dict, traffic: str, tr: dict, limits_of: str) -> str:
    chip = root / "benchmarks" / "chip"
    workload = f"{config}.{traffic}"
    (chip / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    (chip / "traffic" / f"{traffic}.json").write_text(json.dumps(tr))
    shutil.copy(chip / "limits" / f"{limits_of}.json", chip / "limits" / f"{workload}.json")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({"name": config, "source": cfg["source"], "reduced": [],
                                "file": f"benchmarks/chip/configs/{config}.json", "why": "test"})
    manifest["workloads"].append({"name": workload, "config": config, "traffic": traffic,
                                  "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return workload


def test_a_family_added_as_new_files_is_picked_up(tmp_path):
    root = _checkout(tmp_path)
    (root / "benchmarks" / "chip" / "families" / "dense_qk.py").write_text(QK_FAMILY)
    _, _, cfg, tr = tiny("smollm-135m.sync-fedavg")
    assert not cfg.get("qk_norm")
    workload = _add_cell(root, "smollm-qk-tiny",
                         dict(cfg, name="smollm-qk-tiny", family="dense_qk"),
                         "sync-fedavg-tiny", tr, "smollm-135m.sync-fedavg")
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run([sys.executable, "-c", WHOLE_RUN, str(root), str(ROOT / "src"), workload,
                          str(SEED)], cwd=root, env=env, capture_output=True, text=True,
                         timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert Path(out["family_file"]).parent == root / "benchmarks" / "chip" / "families"
    assert "q_norm" in out["shapes"]["dense_layers"]["attn"]
    assert out["correct"], out["checked"]
    for name, c in out["checked"].items():
        assert c["value"] <= 1e-5, (name, c)
    assert out["attempted"] >= 1 and "setup_s" in out["metrics"]


def test_an_unknown_family_is_refused_with_the_names(tmp_path):
    root = _checkout(tmp_path)
    cfg = json.loads((HERE / "configs" / "smollm-135m.json").read_text())
    tr = json.loads((HERE / "traffic" / "sync-fedavg.json").read_text())
    workload = _add_cell(root, "smollm-nope", dict(cfg, name="smollm-nope", family="nope"),
                         "sync-fedavg", tr, "smollm-135m.sync-fedavg")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload", workload,
                          "--seed", "1", "--seconds", "1", "--trace", "0"],
                         cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "{" not in out.stdout
    assert "no model family 'nope'" in out.stderr and "['dense']" in out.stderr, out.stderr
    with pytest.raises(LookupError, match=r"have \['dense'\]"):
        reference.family({"family": "../configs/smollm-135m"})
