"""History sidecar: O(1) per-step payload regression + refusal of
steps outside the sidecar layout.

The sidecar contract (checkpoint/checkpoint.py): everything that grows
with run length streams into ``history.jsonl``; the per-step payload
holds only BOUNDED control state, so checkpoint size must stay flat as
the run gets longer. Every engine save stamps its step with ``engine``
and ``history_offset``; a step without them is not resumed.
"""
import json
import os

import pytest

from repro.api import (ClientPopulationSpec, RuntimeSpec, ScenarioSpec,
                       TaskSpec, run_scenario)
from tests.test_crash_injection import _arch_sync_spec, _sync_fed_spec
from tests.test_crash_injection import _async_spec as _crash_async_spec


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


# ------------------------------------------------ unstamped steps


def _tree_bytes(d):
    """Every file under ``d`` with its bytes, to prove nothing changed."""
    out = {}
    for root, _, files in os.walk(d):
        for f in files:
            path = os.path.join(root, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, d)] = fh.read()
    return out


_SPECS = {"sync": _arch_sync_spec, "sync_fed": _sync_fed_spec,
          "async": _crash_async_spec}


@pytest.mark.parametrize("kind", sorted(_SPECS))
def test_resume_refuses_a_step_without_sidecar_stamps(tmp_path, kind):
    """Every engine reads only the layout it writes: a real checkpoint
    whose STEP.json lost its ``engine`` and ``history_offset`` stamps is
    refused with a ValueError naming the step, the key and the
    directory, and the directory is left exactly as it was (no sidecar
    truncation, no stale-step clear)."""
    make_spec = _SPECS[kind]
    d = str(tmp_path / "ck")
    run_scenario(make_spec(d))
    latest = int(open(f"{d}/LATEST").read())
    sp = f"{d}/step_{latest:08d}/STEP.json"
    meta = json.load(open(sp))
    assert meta["engine"] == kind
    del meta["engine"], meta["history_offset"]
    with open(sp, "w") as f:
        json.dump(meta, f)
    before = _tree_bytes(d)
    assert "history.jsonl" in before

    with pytest.raises(ValueError) as err:
        run_scenario(make_spec(d, resume=True))
    msg = str(err.value)
    assert f"step {latest}" in msg and "'engine'" in msg and d in msg
    assert _tree_bytes(d) == before
