"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 benchmarks/chip/calibrate.py --workload smollm-135m.sync-fedavg \
        --seeds 101,102,...  --control 3 --faults 3 --out readings.json

In one process: the compared numbers of sound runs of the system on each
seed (set-up and its first three steps, no window); of the control, the
reference computed in bfloat16 in the system's place; and of runs with a
fault planted under the timed path (``faults.py``). ``limits/<workload>.json``
is written by hand from these, as ``check.py`` describes.
"""
import argparse
import json
import sys
import time
import types

import run


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--control", type=int, default=3, help="seeds (the first n) for the control")
    ap.add_argument("--faults", type=int, default=3, help="seeds (the first n) for each fault")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    manifest, entry, cfg, tr = run.load_cell(args.workload)
    jax, devices = run.start_jax(entry["chips"])
    import check
    import faults

    seeds = [int(s) for s in args.seeds.split(",")]
    out = {"workload": args.workload, "device": devices[0].device_kind, "program": {},
           "control": {}, "faults": {}}
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        c, numbers, ref = readings(args.workload, cfg, tr, seed, jax, check)
        out["program"][seed] = numbers
        if i < args.control:
            ctl = check.replay(cfg, tr, c.rec, c.words, dtype=jax.numpy.bfloat16)
            out["control"][seed] = check.compare(ctl, ref, c.rec.leaves)
        print(f"seed {seed}: {numbers}; control {out['control'].get(seed)} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
        if i < args.faults:
            for name, fault in faults.for_cell(cfg).items():
                _, fnum, _ = readings(args.workload, cfg, tr, seed, jax, check, fault)
                out["faults"].setdefault(name, {})[seed] = fnum
                print(f"seed {seed} fault {name}: {fnum}", flush=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


def readings(workload, cfg, tr, seed, jax, check, fault=None):
    import cell as cell_mod

    c = cell_mod.Cell(cfg, tr, seed, workload, run.CompileEvents.shared(), fault)
    c.build()
    c.precompile()
    c.run(0.0)
    c.free()
    jax.clear_caches()
    prog = check.program_readings(c.rec)
    ref = check.replay(cfg, tr, c.rec, c.words)
    numbers = check.compare(prog, ref, c.rec.leaves)
    numbers["foreign_rows"] = check.foreign_rows(c.rec, c.data, tr["seq"])
    return c, numbers, ref


if __name__ == "__main__":
    sys.exit(main())
