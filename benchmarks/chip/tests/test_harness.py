"""The benchmark harness on the CPU: the manifest's form, lookup by name,
the counts, the refusal without a chip, and whole runs at a tiny size in
which the reference agrees with the system and planted faults turn
`correct` false.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""
import json
import os
import re
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]

import counts  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in MANIFEST["workloads"]]


# ------------------------------------------------------------ manifest


def test_manifest_keys_and_names():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    names = [c["name"] for c in MANIFEST["configs"]] + WORKLOADS + [
        m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in MANIFEST["workloads"]]:
        assert NAME.match(n), n
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        for key in c["reduced"]:
            assert NAME.match(key), key
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    texts = [w["why"] for w in MANIFEST["workloads"] + MANIFEST["configs"]] + [
        c["source"] for c in MANIFEST["configs"]] + [
        m["layer"] for m in MANIFEST["per_layer"]] + MANIFEST["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t
    for path in (ROOT / p for p in MANIFEST["paths"]):
        for f in path.rglob("*"):
            if "__pycache__" not in f.parts:
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", str(f.relative_to(ROOT))), f


def test_per_layer_metrics_move_a_metric_their_cells_report():
    reported = {w: {m["name"] for m in run.metrics_for(MANIFEST, w, "end_to_end")}
                for w in WORKLOADS}
    for m in MANIFEST["per_layer"]:
        for w in m["workloads"]:
            assert w in reported and m["moves"] in reported[w], (m["name"], w)
    for w in WORKLOADS:
        assert "setup_s" in reported[w] and len(reported[w]) >= 2
        assert run.metrics_for(MANIFEST, w, "per_layer")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves_its_files_by_name(workload):
    manifest, entry, cfg, tr = run.load_cell(workload)
    assert cfg["name"] == entry["config"] and tr["mode"] in ("sync", "async")
    assert (HERE / "limits" / f"{workload}.json").is_file()
    for m in run.metrics_for(manifest, workload, "per_layer"):
        assert callable(run.reader(m["name"]))


def test_a_cell_added_as_new_files_is_picked_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    chip = root / "benchmarks" / "chip"
    (chip / "traffic" / "sync-fedavg-long.json").write_text(
        json.dumps(dict(json.loads((chip / "traffic" / "sync-fedavg.json").read_text()),
                        seq=512)))
    (chip / "limits" / "smollm-135m.sync-fedavg-long.json").write_text(
        (chip / "limits" / "smollm-135m.sync-fedavg.json").read_text())
    (chip / "metrics" / "rounds_traced.py").write_text("def read(ctx):\n    return ctx.steps\n")
    manifest["workloads"].append({"name": "smollm-135m.sync-fedavg-long", "config": "smollm-135m",
                                  "traffic": "sync-fedavg-long", "chips": 1, "why": "test"})
    manifest["per_layer"].append({"name": "rounds_traced", "unit": "1", "better": "higher",
                                  "source": "device_trace", "layer": "device",
                                  "moves": "tokens_per_s",
                                  "workloads": ["smollm-135m.sync-fedavg-long"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    _, entry, cfg, tr = run.load_cell("smollm-135m.sync-fedavg-long", root)
    assert tr["seq"] == 512 and cfg["name"] == "smollm-135m"
    names = [m["name"] for m in run.metrics_for(manifest, entry["name"], "per_layer")]
    assert names == ["rounds_traced"]
    assert run.reader("rounds_traced", chip)(types.SimpleNamespace(steps=7)) == 7


# ------------------------------------------------------------ counts


def test_smollm_flops_per_token_by_hand():
    cfg = json.loads((HERE / "configs" / "smollm-135m.json").read_text())
    per_layer = 576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536
    matmul = 30 * per_layer + 576 * 49152
    assert counts.matmul_params(cfg) == matmul == 134_479_872
    assert counts.train_flops_per_token(cfg, 256) == 6 * matmul + 12 * 30 * 9 * 64 * 256
    assert abs(counts.train_flops_per_token(cfg, 256) / 1e9 - 0.86) < 0.005


def test_trained_params_and_fold_bytes_by_hand():
    cfg = json.loads((HERE / "configs" / "smollm-135m.json").read_text())
    n = counts.trained_params(cfg, 49152)
    # the flat update of the system's smollm-135m, as the chip's trace shows it
    assert n == 134_515_008
    assert counts.fedavg_fold_bytes(4, n) == (4 * n + n) * 4
    assert counts.fedadam_fold_bytes(4, n) == (4 * n + 5 * n) * 4


# ------------------------------------------------------------ refusal


def _run_harness(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload", WORKLOADS[0],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run_harness(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "{" not in out.stdout


def test_checkout_of_only_the_benchmark_exits_nonzero(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = _run_harness(tmp_path)
    assert out.returncode != 0 and "{" not in out.stdout


# ------------------------------------------------------------ whole runs


def cell_files(workload):
    """A cell's configuration and traffic files, found by name: the cells
    with a limits file, whether or not the manifest lists them yet."""
    for c in (HERE / "configs").glob("*.json"):
        if workload.startswith(c.stem + "."):
            traffic = HERE / "traffic" / f"{workload[len(c.stem) + 1:]}.json"
            return json.loads(c.read_text()), json.loads(traffic.read_text())
    raise KeyError(workload)


def tiny(workload):
    """The cell at a tiny size: widths, depth and vocabulary cut, the rest
    as in its files."""
    cfg, tr = cell_files(workload)
    manifest = dict(MANIFEST, workloads=[{"name": workload, "chips": 1}])
    entry = manifest["workloads"][0]
    cfg = dict(cfg, **reference.family(cfg).TINY)
    if cfg.get("head_dim"):
        cfg["head_dim"] = 16
    tr = dict(tr, seq=32, shards_per_client=min(tr["shards_per_client"], 64))
    tr["partner"] = dict(tr["partner"], seq=32)
    return manifest, entry, cfg, tr


def whole_run(workload, fault=None, seed=4_294_967_311):
    import jax

    manifest, entry, cfg, tr = tiny(workload)
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=0.5, trace=0)
    return run.run_cell(args, manifest, entry, cfg, tr, jax, jax.devices(), fault=fault)


# every cell with calibrated limits: the manifest's, and those whose
# harness paths are ready for a later benchmark change (PERF.md, Open questions)
ONE_CHIP = sorted(p.stem for p in (HERE / "limits").glob("*.json"))


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_reference_agrees_with_the_system(workload):
    out = whole_run(workload)
    assert out["correct"], out["checked"]
    for name, c in out["checked"].items():
        assert c["value"] <= 1e-5, (name, c)
    assert out["attempted"] >= 1 and "setup_s" in out["metrics"]


@pytest.mark.parametrize("workload", ONE_CHIP)
@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_planted_fault_is_not_correct(workload, fault):
    import faults
    from cell import task_name

    _, _, cfg, _ = tiny(workload)
    plant = {"half_batch": faults.HalfBatch, "state_unchanged": faults.StateUnchanged}[fault]
    out = whole_run(workload, plant(task_name(cfg)))
    assert not out["correct"], out["checked"]


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_in_bfloat16_is_not_correct(workload):
    """The reference computed in bfloat16, put in the system's place, fails
    the cell's limits (on the chip, at the cell's size, calibrate.py reads
    the same on three seeds)."""
    import jax
    import jax.numpy as jnp

    import calibrate
    import check

    _, _, cfg, tr = tiny(workload)
    c, numbers, ref = calibrate.readings(workload, cfg, tr, 4_294_967_311, jax, check)
    limits = check.load_limits(HERE, workload)
    assert check.judge(numbers, limits), numbers
    ctl = check.compare(check.replay(cfg, tr, c.rec, c.words, dtype=jnp.bfloat16), ref,
                        c.rec.leaves)
    assert not check.judge(dict(ctl, foreign_rows=0), limits), ctl
