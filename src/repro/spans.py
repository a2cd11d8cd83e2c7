"""Spans and counters at the MMFL engines' layer boundaries.

Spans are JAX profiler annotations named ``mmfl.<name>``: they record on
the host thread's line of the profiler's own trace, on the clock of the
device operations, exactly while a JAX profiler capture records
(``jax.profiler.trace``, TensorBoard, Perfetto). Counters count only
while a capture records, so they cover the captured steps alone.
Outside a capture a span costs well under a microsecond.
"""
from collections import Counter

from jax.profiler import StepTraceAnnotation, TraceAnnotation

_counts: Counter = Counter()


def span(name: str) -> TraceAnnotation:
    return TraceAnnotation("mmfl." + name)


def step(name: str, n: int) -> StepTraceAnnotation:
    """A step marker (a round, a flush), as profiler tools group them."""
    return StepTraceAnnotation("mmfl." + name, step_num=n)


def count(name: str, k: int = 1) -> None:
    if TraceAnnotation.is_enabled():
        _counts[name] += k


def fetch(x) -> float:
    """A device value read to the host, counted as ``host_syncs``."""
    count("host_syncs")
    return float(x)


def counters() -> dict:
    """What was counted under captures since the last ``reset()``."""
    return dict(_counts)


def reset() -> None:
    _counts.clear()
