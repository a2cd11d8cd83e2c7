"""Chip benchmark of concurrent federated training (MMFL) on a TPU.

    python3 benchmarks/chip/run.py --workload smollm-135m.sync-fedavg \
        --seed 7 --seconds 20 --trace 0

Runs one cell of ``BENCHMARK.json`` from the root of a checkout: the
cell's configuration file, traffic file, per-layer metric readers and
limits are found by name under ``benchmarks/chip/``. With ``--trace 0``
it prints the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the first seconds of
the window. The last line of standard output is the result as JSON; the
numbers the correctness check compared, each beside its limit, are the
last lines of standard error. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_SECONDS = 5.0                      # length of the traced part of a --trace 1 window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec",
                  "/jax/core/compile/jaxpr_trace_duration")


class Refused(SystemExit):
    """Exit non-zero with a reason and no result line."""

    def __init__(self, why: str):
        super().__init__(f"benchmark: {why}")


def load_cell(workload: str, root: Path = ROOT):
    """The manifest, the cell's entry, and its configuration and traffic
    files, found by name; refused where the configuration's model family
    has no module."""
    import reference

    here = root / "benchmarks" / "chip"
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cfg = json.loads((root / config["file"]).read_text())
    tr = json.loads((here / "traffic" / f"{cell['traffic']}.json").read_text())
    try:
        reference.family(cfg)
    except LookupError as e:
        raise Refused(str(e)) from None
    return manifest, cell, cfg, tr


def metrics_for(manifest: dict, workload: str, kind: str) -> list:
    """The end-to-end or per-layer metric entries this cell reports."""
    return [m for m in manifest[kind] if workload in m.get("workloads", [workload])]


def reader(name: str, here: Path = HERE):
    """The per-layer metric's reader, ``metrics/<name>.py``'s ``read``."""
    spec = importlib.util.spec_from_file_location(f"metric_{name}", here / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileEvents:
    """Counts JAX's compile and cache-load events, to show that none
    happen inside the window."""

    _shared = None

    def __init__(self):
        import jax

        self.counts, self.seconds, self._mark = {}, {}, {}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    @classmethod
    def shared(cls) -> "CompileEvents":
        """One counter per process: a listener cannot be taken off again."""
        if cls._shared is None:
            cls._shared = cls()
        return cls._shared

    def _event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.counts[event] = self.counts.get(event, 0) + 1
            self.seconds[event] = self.seconds.get(event, 0.0) + duration

    def summary(self) -> str:
        return ", ".join(f"{k.rsplit('/', 1)[-1]} {self.counts[k]} ({self.seconds[k]:.1f} s)"
                         for k in sorted(self.counts))

    def mark(self):
        self._mark = dict(self.counts)

    def since_mark(self) -> dict:
        return {k.rsplit("/", 1)[-1]: v - self._mark.get(k, 0)
                for k, v in self.counts.items() if v - self._mark.get(k, 0)}


def start_jax(chips: int):
    """Import the system and JAX; refuse without enough TPU chips."""
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused("the system under test (src/repro) is not in this checkout")
    sys.path[:0] = [str(ROOT / "src"), str(HERE), str(HERE / "metrics")]
    import jax

    # the compile cache lives in the checkout, at the path the system's own
    # entry points use; every program is cached, however fast it compiles
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (devices: {devices})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} TPU chips, JAX found {len(devices)}")
    return jax, devices


def p95(values) -> float:
    if len(values) < 2:
        return max(values)
    return statistics.quantiles(values, n=20, method="inclusive")[18]


def say(*parts):
    print(*parts, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise Refused("--seed must be a non-negative whole number")
    manifest, entry, cfg, tr = load_cell(args.workload)
    jax, devices = start_jax(entry["chips"])
    result = run_cell(args, manifest, entry, cfg, tr, jax, devices)
    for name, c in result["checked"].items():
        print(f"checked {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(args, manifest, entry, cfg, tr, jax, devices, fault=None) -> dict:
    import cell as cell_mod
    import check

    workload = args.workload
    events = CompileEvents.shared()
    c = cell_mod.Cell(cfg, tr, args.seed, workload, events, fault)
    trace_dir = ROOT / ".bench_trace" / workload
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    hooks = {}
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        hooks = {"on_start": lambda: jax.profiler.start_trace(str(trace_dir)),
                 "on_close": jax.profiler.stop_trace}
    t_jax = time.perf_counter()
    c.build()
    t_built = time.perf_counter()
    c.precompile()
    t_pre = time.perf_counter()
    c.run(seconds, **hooks)
    win = c.win
    setup_s = win.start - T0
    window_s = win.end - win.start
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    say(f"set-up: JAX and the system imported, chips found at {t_jax - T0:.3f} s; engine built "
        f"and weights placed at {t_built - T0:.3f} s; precompiled at {t_pre - T0:.3f} s; "
        f"boundaries of the set-up steps at ({', '.join(f'{t - T0:.3f}' for t in c.warmup)}) s; "
        f"done at {setup_s:.3f} s; compile events in set-up: {c.win.setup_compiles}")
    slow = sorted(range(len(win.durations)), key=lambda i: -win.durations[i])[:3]
    say("slowest in the window: " + ", ".join(f"#{i} {win.durations[i] * 1e3:.1f} ms" for i in slow))
    say(f"set-up {setup_s:.3f} s; "
        f"window {window_s:.3f} s, {len(win.durations)} {'rounds' if c.sync else 'flushes'}, "
        f"compiles in the window: {win.compiles.get('backend_compile_duration', 0)}, "
        f"loads from the compile cache: {win.compiles.get('cache_retrieval_time_sec', 0)}, "
        f"traces: {win.compiles.get('jaxpr_trace_duration', 0)}")
    say(f"tokens in the window: {cfg['name']} {win.tokens}, partner {cfg['partner_tiny']} (tiny, "
        f"not counted) {win.partner_tokens}; peak device memory {peak} bytes")
    rec, data, words = c.rec, c.data, c.words
    c.free()
    jax.clear_caches()
    live = sum(a.nbytes for a in jax.live_arrays())
    say(f"device bytes still live after the system's state was freed: {live}")

    prog = check.program_readings(rec)
    ref = check.replay(cfg, tr, rec, words)
    numbers = check.compare(prog, ref, rec.leaves)
    numbers["foreign_rows"] = check.foreign_rows(rec, data, tr["seq"])
    say(f"first {len(rec.steps)} steps: rows all differ {check.distinct_rows(rec, tr['seq'])}; "
        f"program losses {prog['losses'].tolist()}; reference {ref['losses'].tolist()}")
    limits = check.load_limits(HERE, workload)
    correct = check.judge(numbers, limits)

    metrics = {}
    breakdown = None
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if not args.trace:
        values = {"setup_s": setup_s,
                  "tokens_per_s": win.tokens / window_s,
                  "round_ms_p95": p95(win.durations) * 1e3 if c.sync else None,
                  "flush_ms_p95": None if c.sync else p95(win.durations) * 1e3}
        for m in metrics_for(manifest, workload, "end_to_end"):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        say(f"round or flush ms: median {statistics.median(win.durations) * 1e3:.3f}, "
            f"p95 {p95(win.durations) * 1e3:.3f}, max {max(win.durations) * 1e3:.3f}")
    else:
        import devtrace as trace_mod

        tr_data = trace_mod.load(trace_mod.find(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(trace=tr_data, window_s=window_s, tokens=win.tokens,
                      steps=len(win.durations), cfg=cfg, tr=tr, chips=entry["chips"],
                      peaks=json.loads((HERE / "peaks.json").read_text())[devices[0].device_kind],
                      log=say)
        device["busy_s"] = tr_data.busy_s()
        device["window_s"] = window_s
        for m in metrics_for(manifest, workload, "per_layer"):
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr_data.top_ops(), "idle_gaps": tr_data.idle_gaps()}
    out = {"correct": bool(correct), "attempted": len(win.durations), "failed": win.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checked"] = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    return out


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(e.code, file=sys.stderr, flush=True)
        sys.exit(3)
    except Exception:
        import traceback

        traceback.print_exc()
        sys.exit(1)
