"""Device idle time by the host layer that was running when the device
starved, from the program's ``mmfl.*`` spans (``repro.spans``) on the
trace's host line and the first chip's busy intervals, on one clock.

Each idle interval between the first and last device op goes to the
layer whose span covers it (``mmfl.deltas`` counts as the fold), else to
the control plane where a root span (``mmfl.round``; ``mmfl.event`` and
``mmfl.flush``) covers it, else to no span. Per step means over the
complete ``mmfl.round`` spans, or without rounds the complete
``mmfl.flush`` spans: a step that a capture cuts at either end is not
recorded, or, where it is, touches the trace's first or last event.
"""
from __future__ import annotations

import devtrace

LAYERS = (("assemble", ("mmfl.assemble",)), ("cohort", ("mmfl.cohort",)),
          ("fold", ("mmfl.fold", "mmfl.deltas")), ("eval", ("mmfl.eval",)))
ROOTS = ("mmfl.round", "mmfl.event", "mmfl.flush")
PARTS = ("control", "assemble", "cohort", "fold", "eval", "outside")


def _overlap(a: list, b: list) -> list:
    """The intersection of two sorted lists of disjoint [start, end]."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _minus(a: list, b: list) -> list:
    """``a`` less ``b``, both sorted lists of disjoint [start, end]."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def _ns(intervals) -> float:
    return sum(e - s for s, e in intervals)


def decomposition(ctx) -> dict | None:
    """Idle ns under each of ``PARTS`` in the whole trace, the idle total
    and the complete steps; None where the trace holds no ``mmfl.*`` span
    or no complete step. Computed and printed once a run."""
    if not hasattr(ctx, "mmfl_idle"):
        ctx.mmfl_idle = _decompose(ctx)
    return ctx.mmfl_idle


def _decompose(ctx) -> dict | None:
    spans = [(n, s, e) for n, s, e in ctx.trace.python if n.startswith("mmfl.")]
    if not spans:
        return None
    ops = ctx.trace.first().ops
    busy = devtrace.merged((s, e) for _, s, e in ops)
    idle = [[a[1], b[0]] for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    out, left = {}, idle
    for layer, names in LAYERS:
        under = devtrace.merged((s, e) for n, s, e in spans if n in names)
        out[layer] = _ns(_overlap(left, under))
        left = _minus(left, under)
    roots = devtrace.merged((s, e) for n, s, e in spans if n in ROOTS)
    out["control"] = _ns(_overlap(left, roots))
    out["outside"] = _ns(_minus(left, roots))
    out["idle"] = _ns(idle)
    kind = "mmfl.round" if any(n == "mmfl.round" for n, _, _ in spans) else "mmfl.flush"
    events = list(ctx.trace.python) + list(ops)
    lo, hi = min(s for _, s, _ in events), max(e for _, _, e in events)
    out["steps"] = sum(n == kind and lo < s and e < hi for n, s, e in spans)
    if not out["steps"]:
        return None
    per = ", ".join(f"{p} {out[p] / 1e6 / out['steps']:.3f}" for p in PARTS)
    ctx.log(f"device idle by host layer, ms per step over {out['steps']} complete {kind} "
            f"spans: {per} (fold with deltas; outside: under no span); the parts sum to "
            f"{sum(out[p] for p in PARTS) / 1e6:.3f} ms of {out['idle'] / 1e6:.3f} ms idle "
            f"between the first and last op; window {ctx.window_s * 1e3:.3f} ms, idle in it "
            f"{(ctx.window_s - ctx.trace.busy_s()) * 1e3:.3f} ms")
    return out


def _idle_ms(part: str):
    def read(ctx):
        d = decomposition(ctx)
        return None if d is None else d[part] / 1e6 / d["steps"]
    return read


control_idle_ms = _idle_ms("control")
assemble_idle_ms = _idle_ms("assemble")
fold_idle_ms = _idle_ms("fold")


def host_syncs(ctx):
    """Device values read to the host (``repro.spans.fetch``) per step."""
    d = decomposition(ctx)
    if d is None:
        return None
    from repro import spans

    return spans.counters().get("host_syncs", 0) / d["steps"]


def cohort_fill(ctx):
    """Share of the backends' cohort rows that were real clients, not
    padding (``cohort_rows`` over ``cohort_padded_rows``)."""
    d = decomposition(ctx)
    if d is None:
        return None
    from repro import spans

    c = spans.counters()
    if not c.get("cohort_padded_rows"):
        return None
    return 100.0 * c["cohort_rows"] / c["cohort_padded_rows"]
