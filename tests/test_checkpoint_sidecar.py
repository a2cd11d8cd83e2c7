"""History sidecar: O(1) per-step payload regression + compatibility
with legacy (embedded-history) checkpoints.

The sidecar contract (checkpoint/checkpoint.py): everything that grows
with run length streams into ``history.jsonl``; the per-step payload
holds only BOUNDED control state, so checkpoint size must stay flat as
the run gets longer. Checkpoints written before the sidecar embedded
the whole-run curves inside STEP.json — those must keep resuming, with
the sidecar backfilled so the next save commits new-layout history.
"""
import json
import os
import shutil

import jax
import numpy as np

from repro.api import (ClientPopulationSpec, RuntimeSpec, ScenarioSpec,
                       TaskSpec, run_scenario)
from tests.test_async_resume import assert_async_equal
from tests.test_crash_injection import assert_sync_equal

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


# ----------------------------------------------------- O(1) regression


def _async_spec(arrivals, d, resume=False):
    return ScenarioSpec(
        name="o1-size", seed=0,
        tasks=[TaskSpec("synth-mnist", options={"n_range": [30, 40]}),
               TaskSpec("synth-fmnist", options={"n_range": [30, 40]})],
        clients=ClientPopulationSpec(n_clients=8, speed_profile="bimodal"),
        runtime=RuntimeSpec(mode="async", tau=1, total_arrivals=arrivals,
                            buffer_size=2, checkpoint_dir=d,
                            checkpoint_every=4, checkpoint_keep=1,
                            resume=resume))


def test_step_payload_is_o1_in_run_length(tmp_path):
    """Regression: 10x the flush count must leave the per-step
    checkpoint payload flat (bounded control state only) while the
    sidecar absorbs the growth. This is THE property that keeps
    long-run checkpointing O(1) — before the sidecar, STEP.json grew
    linearly with every flush."""
    def sizes(arrivals):
        d = str(tmp_path / f"run{arrivals}")
        run_scenario(_async_spec(arrivals, d))
        latest = int(open(f"{d}/LATEST").read())
        step = os.path.getsize(f"{d}/step_{latest:08d}/STEP.json")
        sidecar = os.path.getsize(f"{d}/{'history.jsonl'}")
        return step, sidecar

    step_1x, sidecar_1x = sizes(20)
    step_10x, sidecar_10x = sizes(200)
    # flat payload: a small constant of slack (retained-version table,
    # float formatting), nothing proportional to the 10x event count
    assert step_10x < step_1x * 1.25 + 512, (step_1x, step_10x)
    # the growth went to the sidecar instead
    assert sidecar_10x > 5 * sidecar_1x, (sidecar_1x, sidecar_10x)


# ------------------------------------------- legacy embedded-history


def test_legacy_async_checkpoint_fixture_resumes(tmp_path):
    """A COMMITTED pre-sidecar checkpoint (fixtures/legacy_ckpt_async:
    history embedded in STEP.json, no engine stamp, no history_offset)
    resumes under the current code: curves cover the WHOLE run and
    match the recorded uninterrupted result, and the resume backfills
    the sidecar so the directory is upgraded to the new layout."""
    fix = os.path.join(FIXTURES, "legacy_ckpt_async")
    d = str(tmp_path / "ck")
    shutil.copytree(os.path.join(fix, "ckpt"), d)
    doc = open(os.path.join(fix, "spec.json")).read().replace("__CKPT__", d)
    spec = ScenarioSpec.from_json(doc)
    # checkpoint every flush so the short post-resume tail (3 flushes)
    # reaches a save and COMMITS the backfilled sidecar
    spec.runtime.checkpoint_every = 1
    expected = json.load(open(os.path.join(fix, "expected.json")))

    meta = json.load(open(f"{d}/step_00000004/STEP.json"))
    assert "history_offset" not in meta and "engine" not in meta
    assert not os.path.exists(f"{d}/history.jsonl")

    # The fixture was recorded with JAX's pre-0.5 random bits: since 0.5,
    # jax_threefry_partitionable defaults to True and draws other bits
    # from the same keys, so the retrained tail would train on other
    # minibatches (and, for these synthetic tasks, loss is 1 - test
    # accuracy). Resuming under the recording's stream keeps every
    # comparison below as strict as when the fixture was made.
    with jax.threefry_partitionable(False):
        res = run_scenario(spec)
    # the full-run curves, not just the post-resume tail; the restored
    # prefix is exact (pure JSON replay), the retrained tail allclose
    np.testing.assert_allclose(res.loss, np.asarray(expected["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(res.acc, np.asarray(expected["acc"]),
                               rtol=1e-5)
    np.testing.assert_array_equal(res.time, np.asarray(expected["time"]))
    np.testing.assert_array_equal(res.arrivals,
                                  np.asarray(expected["arrivals"]))
    np.testing.assert_array_equal(res.versions,
                                  np.asarray(expected["versions"]))
    np.testing.assert_array_equal(res.buffer_sizes,
                                  np.asarray(expected["buffer_sizes"]))
    assert [list(a) for a in res.assignments] == expected["assignments"]
    # resume backfilled the embedded history into the sidecar and the
    # post-resume saves committed it: the directory now speaks the new
    # layout end-to-end
    assert os.path.getsize(f"{d}/history.jsonl") > 0
    latest = int(open(f"{d}/LATEST").read())
    meta = json.load(open(f"{d}/step_{latest:08d}/STEP.json"))
    assert meta["engine"] == "async"
    # events after the final save stay uncommitted past the offset
    assert 0 < meta["history_offset"] <= \
        os.path.getsize(f"{d}/history.jsonl")
    # and a SECOND resume now replays purely from the sidecar
    with jax.threefry_partitionable(False):
        again = run_scenario(spec)
    assert_async_equal(res, again)


def test_legacy_sync_embedded_history_resumes(tmp_path):
    """Sync-engine legacy compat: a new-layout arch checkpoint
    down-converted to the old embedded-history shape (curves inside the
    coordinator payload, no engine stamp, no sidecar) resumes to the
    uninterrupted result through ArchSyncEngine's fallback path."""
    def spec(d=None, resume=False, rounds=2):
        return ScenarioSpec(
            name="legacy-sync",
            tasks=[TaskSpec("smollm-135m", family="arch",
                            options={"preset": "tiny", "seq": 16,
                                     "batch": 2, "tau": 1})],
            clients=ClientPopulationSpec(n_clients=4),
            runtime=RuntimeSpec(mode="sync", rounds=rounds, tau=1,
                                checkpoint_dir=d, checkpoint_every=1,
                                checkpoint_keep=3, resume=resume))

    full = run_scenario(spec())
    d = str(tmp_path / "ck")
    run_scenario(spec(d))
    # keep only step 1 and rewrite it into the legacy layout: embedded
    # history, no engine stamp / history_offset, no sidecar, LATEST at 1
    sp = f"{d}/step_00000001/STEP.json"
    meta = json.load(open(sp))
    with open(f"{d}/history.jsonl", "rb") as f:
        recs = [json.loads(line) for line in
                f.read(meta["history_offset"]).splitlines() if line]
    rounds = [r for r in recs if r["kind"] == "round"]
    meta["coordinator"]["history"] = {
        "loss": [r["loss"] for r in rounds],
        "counts": [r["counts"] for r in rounds],
        "alloc": [r["alloc"] for r in rounds],
        "acc": [r["acc"] for r in rounds],
        "wall_clock": [r["wall_clock"] for r in rounds],
    }
    del meta["engine"], meta["history_offset"]
    with open(sp, "w") as f:
        json.dump(meta, f)
    os.remove(f"{d}/history.jsonl")
    shutil.rmtree(f"{d}/step_00000002")
    with open(f"{d}/LATEST", "w") as f:
        f.write("1")

    resumed = run_scenario(spec(d, resume=True))
    assert_sync_equal(full, resumed)
    # the resume backfilled + committed new-layout history at step 2
    meta2 = json.load(open(f"{d}/step_00000002/STEP.json"))
    assert meta2["engine"] == "sync"
    assert meta2["history_offset"] == os.path.getsize(f"{d}/history.jsonl")
