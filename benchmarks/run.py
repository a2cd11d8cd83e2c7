"""Benchmark gate: one section per paper table/figure. Prints
``name,us_per_call,derived`` CSV lines, ``us_per_call`` being the
experiment's wall time on this host (a CPU run: not a device speed; the
chip benchmark is ``benchmarks/chip/run.py``).

  PYTHONPATH=src python -m benchmarks.run [--full]

--full runs paper-sized experiments (slow); default is the fast CI gate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def experiment_specs():
    from benchmarks import experiments as E

    return [
        ("exp1_difficulty_fig2", E.exp1_difficulty),
        ("exp2_task_count_fig3", E.exp2_task_count),
        ("exp3_client_count_fig4", E.exp3_client_count),
        ("exp4_auctions_fig5ab", E.exp4_auctions),
        ("exp5_auction_learning_fig5c", E.exp5_auction_learning),
        ("exp6_alpha_sweep_techreport", E.exp6_alpha_sweep),
        ("exp7_stragglers_extension", E.exp7_stragglers),
        ("exp8_tau_sweep_extension", E.exp8_tau_sweep),
        ("exp9_async_vs_sync_fedast", E.exp9_async_vs_sync),
        ("exp10_backend_scaling", E.exp10_backend_scaling),
        ("exp11_policy_comparison", E.exp11_policy_comparison),
        ("exp12_adaptive_buffers", E.exp12_adaptive_buffers),
        ("exp13_aggregators", E.exp13_aggregators),
        ("exp14_cost_models", E.exp14_cost_models),
        ("exp15_population_scaling", E.exp15_population_scaling),
        ("exp16_static_analysis", E.exp16_static_analysis),
        ("exp17_checkpoints", E.exp17_checkpoints),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-sized experiment runs (slow)")
    ap.add_argument("--skip-experiments", action="store_true")
    ap.add_argument("--list", action="store_true",
                    help="print experiment names and exit")
    ap.add_argument("--only", default=None, metavar="NAME",
                    help="run a single experiment (full name or unique "
                         "prefix, e.g. 'exp4')")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: the async-vs-sync experiment only "
                         "(alias for --only exp9)")
    ap.add_argument("--json-out", default=None,
                    help="also write the rows as JSON (CI artifact)")
    ap.add_argument("--sweep", default=None, metavar="SPEC_JSON",
                    help="ScenarioSpec JSON file: run a grid sweep over "
                         "it (see --grid) instead of the experiments")
    ap.add_argument("--grid", default=None, metavar="GRID",
                    help="sweep grid: JSON object of dotted-path -> "
                         "value list (inline or @file), e.g. "
                         "'{\"runtime.backend\": [\"serial\", \"vmap\"]}'")
    ap.add_argument("--jobs", type=int, default=1, metavar="N",
                    help="run --sweep grid points in N worker processes "
                         "(deterministic grid-order results either way; "
                         "CPU-only: needs JAX_PLATFORMS=cpu)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    fast = not args.full
    rows = []

    if args.list:
        for name, _ in experiment_specs():
            print(name)
        return

    if args.sweep:
        from repro.api import ScenarioSpec, sweep_scenarios

        grid_text = args.grid or "{}"
        if grid_text.startswith("@"):
            with open(grid_text[1:]) as f:
                grid_text = f.read()
        merged = sweep_scenarios(ScenarioSpec.load(args.sweep),
                                 json.loads(grid_text), verbose=True,
                                 max_workers=args.jobs)
        out = args.json_out or "BENCH_sweep.json"
        with open(out, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
        print(f"# sweep: {len(merged['runs'])} runs -> {out}",
              file=sys.stderr)
        return

    if not args.skip_experiments:
        specs = experiment_specs()
        only = args.only or ("exp9" if args.smoke else None)
        if only:
            exact = [(n, f) for n, f in specs if n == only]
            # token-boundary prefix first, so --only exp1 stays unique
            # now that exp10 exists
            matched = (exact
                       or [(n, f) for n, f in specs
                           if n.startswith(only + "_")]
                       or [(n, f) for n, f in specs
                           if n.startswith(only)])
            if not matched:
                sys.exit(f"--only {only!r} matches no experiment; "
                         "see --list")
            if len(matched) > 1:
                sys.exit(f"--only {only!r} is ambiguous: "
                         + ", ".join(n for n, _ in matched))
            specs = matched
        for name, fn in specs:
            t0 = time.perf_counter()
            result = fn(fast=fast)
            us = (time.perf_counter() - t0) * 1e6
            rows.append((name, us, json.dumps(result, sort_keys=True)))
            print(f"# {name}: {json.dumps(result, sort_keys=True)[:220]}",
                  file=sys.stderr)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        d = str(derived).replace(",", ";")
        print(f"{name},{us:.1f},{d}")

    if args.json_out:
        payload = {name: {"us_per_call": us, "derived": derived}
                   for name, us, derived in rows}
        with open(args.json_out, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)


if __name__ == "__main__":
    main()
