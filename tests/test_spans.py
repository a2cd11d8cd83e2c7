"""The MMFL spans and counters (``repro.spans``): recorded under a JAX
profiler capture, nested by layer inside each round or flush on one host
line, and counting what the scenario implies; outside a capture nothing
is counted."""
import glob

import jax
import numpy as np
import pytest

from repro import spans
from repro.api import (ClientPopulationSpec, RuntimeSpec, ScenarioSpec,
                       TaskSpec, run_scenario)

LAYERS = {"mmfl.assemble", "mmfl.cohort", "mmfl.fold", "mmfl.eval"}
ROWS = 3                      # cohort rows a task round; vmap pads them to 4


def _arch(name, tau):
    return TaskSpec(name, family="arch",
                    options={"preset": "tiny", "seq": 16, "batch": ROWS, "tau": tau})


def _captured(tmp_path, spec):
    """Run ``spec`` under a profiler capture; its result, the ``mmfl.*``
    events by host line, and the counters of the capture."""
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        result = run_scenario(spec)
    counted = spans.counters()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("mmfl.")]
            if evs:
                lines[(plane.name, line.name)] = evs
    assert len(lines) == 1, list(lines)          # every span on one host line
    return result, next(iter(lines.values())), counted


def _inside(ev, outer):
    return any(s <= ev[1] and ev[2] <= e for _, s, e in outer)


def test_count_outside_a_capture_records_nothing():
    spans.reset()
    spans.count("host_syncs")
    assert spans.fetch(np.float32(2.5)) == 2.5
    assert spans.counters() == {}


def test_sync_round_spans_nest_and_counters_match(tmp_path):
    spec = ScenarioSpec(
        name="spans-sync", seed=3,
        tasks=[_arch("smollm-135m", 2), _arch("qwen3-0.6b", 2)],
        clients=ClientPopulationSpec(n_clients=4, participation=1.0),
        runtime=RuntimeSpec(mode="sync", backend="vmap", rounds=2, tau=2))
    result, evs, counted = _captured(tmp_path, spec)
    rounds = [e for e in evs if e[0] == "mmfl.round"]
    layers = [e for e in evs if e[0] in LAYERS]
    assert len(rounds) == 2
    assert {e[0] for e in layers} == LAYERS
    assert all(_inside(e, rounds) for e in layers)
    served = int((result.alloc_counts > 0).sum())   # task rounds that got clients
    assert served >= 2
    assert sum(e[0] == "mmfl.cohort" for e in layers) == served
    # each served task reads its loss; every task reads its accuracy each round
    assert counted["host_syncs"] == served + 2 * len(spec.tasks)
    assert counted["cohort_rows"] == ROWS * served
    assert counted["cohort_padded_rows"] == 4 * served


def test_async_flush_spans_nest_and_counters_match(tmp_path):
    spec = ScenarioSpec(
        name="spans-async", seed=3, tasks=[_arch("smollm-135m", 1)],
        clients=ClientPopulationSpec(n_clients=4),
        runtime=RuntimeSpec(mode="async", backend="vmap", total_arrivals=ROWS,
                            buffer_size=ROWS))
    result, evs, counted = _captured(tmp_path, spec)
    assert len(result.time) == 1                      # one flush
    events = [e for e in evs if e[0] == "mmfl.event"]
    flushes = [e for e in evs if e[0] == "mmfl.flush"]
    layers = [e for e in evs if e[0] in LAYERS | {"mmfl.deltas"}]
    assert len(events) == ROWS and len(flushes) == 1
    assert _inside(flushes[0], events)
    assert {e[0] for e in layers} == LAYERS | {"mmfl.deltas"}
    assert all(_inside(e, flushes) for e in layers)
    # the start reads the first model's loss and accuracy, the flush the new one's
    assert counted == {"host_syncs": 2 + 2, "cohort_rows": ROWS, "cohort_padded_rows": 4}


@pytest.mark.parametrize("backend,padded", [("serial", ROWS), ("vmap", 4)])
def test_cohort_counters_by_backend(tmp_path, backend, padded):
    from repro.api.backend import ClientBatch, CohortTask, get_backend

    def local_fn(params, key, x):
        return params + x, x.sum()

    batch = ClientBatch(np.arange(ROWS), None, (np.ones((ROWS, 2), np.float32),))
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        get_backend(backend).run_cohort(CohortTask("t", np.zeros(2, np.float32), local_fn), batch)
    assert spans.counters() == {"cohort_rows": ROWS, "cohort_padded_rows": padded}
