"""The span readers (``metrics/_spans.py``) on traces built by hand, with
known device busy intervals and ``mmfl.*`` spans: each layer's idle time
against a hand count, cut steps left out of the divisor, the control
plane without its child layers, and a printed decomposition that sums to
the trace's idle time. Times are in milliseconds, written as ns."""
import re
import types
from collections import Counter

import pytest

import devtrace
import run

MS = 1_000_000
MARKS = [("$profiler.py:101 start_trace", 0, 5), ("$profiler.py:213 stop_trace", 995, 1000)]


def ctx_of(ops, spans, lines=None):
    dev = devtrace.Device(ops=[("%op", s * MS, e * MS) for s, e in ops])
    python = [(n, s * MS, e * MS) for n, s, e in MARKS + spans]
    trace = devtrace.Trace({"/device:TPU:0": dev}, python)
    log = lines.append if lines is not None else (lambda *_: None)
    return types.SimpleNamespace(trace=trace, window_s=1.0, log=lambda *p: log(" ".join(p)))


# sync: two complete rounds, one cut at the start and one at the end of the
# capture; idle by hand: assemble 30+10, cohort 10+30, fold 30+20, eval
# 20+10, control 40+30 in round 2, 2 at its end and 5 in the cut round,
# outside any span 5 between the rounds
SYNC_SPANS = [("mmfl.round", 0, 100),
              ("mmfl.round", 100, 500), ("mmfl.assemble", 110, 150), ("mmfl.cohort", 150, 200),
              ("mmfl.fold", 300, 350), ("mmfl.eval", 400, 450),
              ("mmfl.round", 500, 900), ("mmfl.assemble", 510, 530), ("mmfl.cohort", 530, 560),
              ("mmfl.fold", 700, 720), ("mmfl.eval", 800, 820),
              ("mmfl.round", 905, 1000)]
SYNC_OPS = [(20, 120), (160, 310), (340, 420), (440, 520), (560, 700), (760, 810), (850, 898),
            (910, 960)]
SYNC_MS = {"control": 77 / 2, "assemble": 40 / 2, "cohort": 40 / 2, "fold": 50 / 2,
           "eval": 30 / 2, "outside": 5 / 2}

# async: two complete flushes inside their events, one event that only
# dispatches; the deltas count as the fold
ASYNC_SPANS = [("mmfl.event", 100, 400), ("mmfl.flush", 150, 350), ("mmfl.assemble", 150, 170),
               ("mmfl.cohort", 170, 200), ("mmfl.deltas", 200, 260), ("mmfl.fold", 260, 300),
               ("mmfl.eval", 300, 330),
               ("mmfl.event", 400, 700),
               ("mmfl.event", 700, 990), ("mmfl.flush", 720, 950), ("mmfl.assemble", 720, 730),
               ("mmfl.cohort", 730, 760), ("mmfl.deltas", 760, 800), ("mmfl.fold", 800, 850),
               ("mmfl.eval", 850, 900)]
ASYNC_OPS = [(10, 180), (190, 210), (250, 270), (290, 310), (320, 420), (600, 740), (745, 770),
             (790, 810), (840, 990)]
ASYNC_MS = {"control": 180 / 2, "assemble": 0.0, "cohort": 15 / 2, "fold": 110 / 2,
            "eval": 10 / 2, "outside": 0.0}


@pytest.mark.parametrize("suffix,ops,spans,want", [
    ("round", SYNC_OPS, SYNC_SPANS, SYNC_MS),
    ("flush", ASYNC_OPS, ASYNC_SPANS, ASYNC_MS),
])
def test_idle_ms_by_layer_against_a_hand_count(suffix, ops, spans, want):
    ctx = ctx_of(ops, spans)
    for part in ("control", "assemble", "fold"):
        got = run.reader(f"{part}_idle_ms.{suffix}")(ctx)
        assert got == pytest.approx(want[part]), part
    d = ctx.mmfl_idle
    assert d["steps"] == 2
    for part, ms in want.items():
        assert d[part] / MS / d["steps"] == pytest.approx(ms), part


def test_a_step_cut_at_either_end_is_not_counted():
    inner = [s for s in SYNC_SPANS if s[1:] not in ((0, 100), (905, 1000))]
    first, last = ("mmfl.round", 0, 100), ("mmfl.round", 905, 1000)
    for cut in ([], [first], [last], [first, last]):
        ctx = ctx_of(SYNC_OPS, inner + cut)
        run.reader("control_idle_ms.round")(ctx)
        assert ctx.mmfl_idle["steps"] == 2


def test_control_excludes_its_child_layers():
    bare = [s for s in SYNC_SPANS if s[0] == "mmfl.round"]
    whole = ctx_of(SYNC_OPS, bare)
    assert run.reader("control_idle_ms.round")(whole) == pytest.approx(
        sum(v for k, v in SYNC_MS.items() if k != "outside"))
    assert run.reader("assemble_idle_ms.round")(whole) == 0.0


@pytest.mark.parametrize("ops,spans", [(SYNC_OPS, SYNC_SPANS), (ASYNC_OPS, ASYNC_SPANS)])
def test_printed_decomposition_sums_to_the_idle_time(ops, spans):
    lines = []
    ctx = ctx_of(ops, spans, lines)
    run.reader("fold_idle_ms.round")(ctx)
    run.reader("host_syncs.round")(ctx)
    (line,) = lines                                       # printed once a run
    steps = int(re.search(r"over (\d+) complete", line).group(1))
    parts = re.findall(r"(control|assemble|cohort|fold|eval|outside) ([\d.]+)", line)
    assert [p for p, _ in parts] == ["control", "assemble", "cohort", "fold", "eval", "outside"]
    idle = sum(b[0] - a[1] for a, b in zip(ops, ops[1:]))
    assert sum(float(v) for _, v in parts) * steps == pytest.approx(idle, abs=1e-2)
    assert f"of {idle:.3f} ms idle" in line


def test_counter_readers_per_step_and_fill(monkeypatch):
    from repro import spans

    monkeypatch.setattr(spans, "_counts", Counter(
        {"host_syncs": 7, "cohort_rows": 6, "cohort_padded_rows": 8}))
    ctx = ctx_of(SYNC_OPS, SYNC_SPANS)
    assert run.reader("host_syncs.round")(ctx) == 3.5
    assert run.reader("cohort_fill.flush")(ctx_of(ASYNC_OPS, ASYNC_SPANS)) == 75.0


NEW = ["control_idle_ms.round", "control_idle_ms.flush", "assemble_idle_ms.round",
       "assemble_idle_ms.flush", "fold_idle_ms.round", "fold_idle_ms.flush", "host_syncs.round",
       "host_syncs.flush", "cohort_fill.flush"]


@pytest.mark.parametrize("name", NEW)
def test_a_trace_without_spans_reads_nothing(name):
    """A program without the spans (an older commit) gives no reading,
    and no error."""
    assert run.reader(name)(ctx_of(SYNC_OPS, [])) is None
