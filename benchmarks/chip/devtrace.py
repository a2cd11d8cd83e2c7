"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy intervals, per-program and per-operation device time,
and the device's idle gaps labelled by what the host was doing.

A TPU device is a plane named ``/device:TPU:<n>``. Its ``XLA Ops`` line
holds one event per executed operation, named by the operation's HLO
text (``%name = type op(...)``); its ``XLA Modules`` line holds one
event per program run, named ``<jit name>(<fingerprint>)``. The host
plane ``/host:CPU`` has a line of Python frames, named after the
interpreter (``python``, ``python3``), whose events are named ``$file:line
function``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

_MODULE = re.compile(r"\(\d+\)$")


def module_name(event_name: str) -> str:
    """``jit_local_fn(6545440350093968515)`` -> ``jit_local_fn``."""
    return _MODULE.sub("", event_name)


def op_name(hlo: str) -> str:
    """``%fusion.444 = (...) fusion(...)`` -> ``fusion.444``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_kind(hlo: str) -> str:
    """The HLO opcode of an op's text: ``custom-call``, ``all-reduce``, ..."""
    rhs = hlo.split(" = ", 1)[-1]
    m = re.search(r"\}?\s([a-z][a-z0-9\-]*)\(", rhs)
    return m.group(1) if m else ""


def union_length(intervals) -> int:
    """Total length covered by (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Device:
    ops: list = field(default_factory=list)        # (hlo text, start_ns, end_ns)
    modules: list = field(default_factory=list)    # (module name, start_ns, end_ns)

    def busy_ns(self) -> int:
        return union_length((s, e) for _, s, e in self.ops)

    def module_ns(self, pred) -> int:
        return sum(e - s for m, s, e in self.modules if pred(m))

    def ops_in(self, module_pred):
        """Ops that ran inside a program whose name satisfies the test."""
        spans = sorted((s, e) for m, s, e in self.modules if module_pred(m))
        starts = [s for s, _ in spans]
        out = []
        for hlo, s, e in self.ops:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < spans[i][1]:
                out.append((hlo, s, e))
        return out

    def module_of(self):
        """op start -> enclosing module name, as a lookup function."""
        spans = sorted((s, e, m) for m, s, e in self.modules)
        starts = [s for s, _, _ in spans]

        def find(t):
            i = bisect.bisect_right(starts, t) - 1
            return spans[i][2] if i >= 0 and t < spans[i][1] else "?"
        return find


@dataclass
class Trace:
    devices: dict                                  # plane name -> Device
    python: list                                   # (frame, start_ns, end_ns)

    @property
    def ids(self) -> list:
        return sorted(self.devices, key=lambda n: int(n.rsplit(":", 1)[1]))

    def first(self) -> Device:
        return self.devices[self.ids[0]]

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(d.busy_ns() for d in self.devices.values()) / len(self.devices) / 1e9

    def top_ops(self, n: int = 10) -> list:
        """[program/op, seconds] of the ops that took most time on the first chip."""
        dev = self.first()
        where = dev.module_of()
        tot = defaultdict(int)
        for hlo, s, e in dev.ops:
            tot[f"{where(s)}/{op_name(hlo)}"] += e - s
        return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """[label, seconds] of the longest idle gaps on the first chip,
        labelled by the innermost Python frame that covers the gap, or the
        one that overlaps it most ("unattributed" where none does)."""
        busy = merged((s, e) for _, s, e in self.first().ops)
        gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])
                       if b[0] > a[1]), reverse=True)[:n]
        frames = sorted(self.python, key=lambda f: f[1])
        out = []
        for length, gs, ge in gaps:
            best, best_key = "unattributed", None
            for name, fs, fe in frames:
                if fs >= ge:
                    break
                overlap = min(fe, ge) - max(fs, gs)
                if overlap <= 0:
                    continue
                covers = fs <= gs and fe >= ge
                key = (covers, -(fe - fs) if covers else overlap)
                if best_key is None or key > best_key:
                    best, best_key = name.lstrip("$"), key
            out.append([best, length / 1e9])
        return out


def load(path: str) -> Trace:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, python = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device()
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [(module_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events]
            devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    python += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
    if not devices:
        raise RuntimeError(f"no TPU device plane in {path}")
    return Trace(devices, python)


def find(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}, found {files}")
    return files[0]
