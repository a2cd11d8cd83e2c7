"""The trace reduction on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``record_fixture.py``): three calls of
the Pallas fedavg kernel and of a small matmul, with host sleeps between.
Busy time, idle share and per-kernel time are checked against naive
recomputations from the raw events."""
from pathlib import Path

import numpy as np
import pytest

import devtrace

FIXTURE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return devtrace.load(str(FIXTURE))


def test_one_tpu_device_with_ops_and_programs(tr):
    assert tr.ids == ["/device:TPU:0"]
    dev = tr.first()
    assert dev.ops and dev.modules
    assert {m for m, _, _ in dev.modules} >= {"jit__fedavg_jit", "jit__lambda"}


def test_busy_union_against_a_nanosecond_grid(tr):
    dev = tr.first()
    t0 = min(s for _, s, _ in dev.ops)
    t1 = max(e for _, _, e in dev.ops)
    grid = np.zeros(int(round(t1 - t0)) + 1, bool)
    for _, s, e in dev.ops:
        grid[int(round(s - t0)):int(round(e - t0))] = True
    # event times are fractional nanoseconds: rounding moves each edge by <= 0.5 ns
    assert abs(dev.busy_ns() - int(grid.sum())) <= len(dev.ops)
    # the sleeps between calls leave the device idle most of the span
    assert 0 < dev.busy_ns() < 0.5 * (t1 - t0)
    gaps = tr.idle_gaps(3)
    assert len(gaps) == 3 and all(g[1] > 1e-3 for g in gaps)
    # the host slept, between calls, inside the recorder's main()
    assert all(label != "unattributed" for label, _ in gaps), gaps
    assert sum(g for _, g in tr.idle_gaps(10 ** 6)) == pytest.approx(
        (t1 - t0 - dev.busy_ns()) / 1e9)


def test_kernel_time_and_kind(tr):
    dev = tr.first()
    kernel = [(h, s, e) for h, s, e in dev.ops_in(lambda m: m == "jit__fedavg_jit")
              if devtrace.op_kind(h) == "custom-call"]
    assert len(kernel) == 3
    assert all(devtrace.op_name(h).startswith("_fedavg") for h, _, _ in kernel)
    total = sum(e - s for _, s, e in kernel)
    assert 0 < total < dev.module_ns(lambda m: m == "jit__fedavg_jit")
    top = dict(tr.top_ops(50))
    assert sum(v for k, v in top.items() if k.startswith("jit__fedavg_jit/_fedavg")) == \
        pytest.approx(total / 1e9)
