"""Grid sweeps over ScenarioSpecs: one call, one merged RunResult JSON.

A sweep is a base spec plus a grid of dotted-path overrides — e.g.
``{"runtime.backend": ["serial", "vmap"], "allocation.strategy":
["fedfair", "random"]}`` runs the 2x2 cartesian product through
``run_scenario`` and merges every ``RunResult.to_json()`` into one
payload, so backend x allocation (or any other axis product) comparisons
are a single call instead of driver plumbing:

    from repro.api import sweep_scenarios
    merged = sweep_scenarios(base, {"runtime.backend": ["serial", "vmap"]})

``max_workers=N`` runs the grid points in N worker PROCESSES
(spawn-context ``ProcessPoolExecutor``; each worker re-imports jax and
rebuilds the spec from JSON), with results merged in grid order so the
payload is deterministic regardless of completion order. It is CPU-only
(``JAX_PLATFORMS=cpu``): an accelerator belongs to one process. Grid points
must only reference registry keys importable from ``repro.*`` — a spec
using an in-process custom registration needs the sequential path.

CLI: ``python -m benchmarks.run --sweep spec.json --grid grid.json
[--jobs N]``.
"""

from __future__ import annotations

import copy
import json
import os
import time
from itertools import product
from typing import Any, Dict, List, Optional, Sequence

from repro.api.spec import ScenarioSpec


def apply_override(spec: ScenarioSpec, path: str, value: Any) -> None:
    """Set a dotted-path field on a spec tree (``runtime.backend``,
    ``allocation.alpha``, ``seed``, ...), failing fast on unknown paths."""
    obj: Any = spec
    parts = path.split(".")
    for p in parts[:-1]:
        if not hasattr(obj, p):
            msg = f"sweep override {path!r}: {type(obj).__name__} has no field {p!r}"
            raise AttributeError(msg)
        obj = getattr(obj, p)
    leaf = parts[-1]
    if not hasattr(obj, leaf):
        msg = f"sweep override {path!r}: {type(obj).__name__} has no field {leaf!r}"
        raise AttributeError(msg)
    setattr(obj, leaf, value)


def _sweep_worker(spec_json: str) -> Dict[str, Any]:
    """Run ONE grid point in a worker process. Spawn-safe: the spec
    travels as JSON and the engine import happens inside the worker, so
    nothing unpicklable crosses the process boundary."""
    from repro.api.engine import run_scenario

    spec = ScenarioSpec.from_dict(json.loads(spec_json))
    t0 = time.time()
    result = run_scenario(spec)
    return {"wall_time": time.time() - t0, "result": result.to_json()}


def _grid_points(base_spec: ScenarioSpec, grid: Dict[str, Sequence[Any]]):
    """Materialise the cartesian product as (spec, overrides) pairs, in
    deterministic sorted-axis grid order."""
    axes = sorted(grid)
    for path, values in grid.items():
        if not isinstance(values, (list, tuple)):
            msg = f"grid[{path!r}] must be a list of values, got {type(values).__name__}"
            raise TypeError(msg)
    points = []
    for combo in product(*(grid[a] for a in axes)):
        spec = copy.deepcopy(base_spec)
        overrides = dict(zip(axes, combo))
        for path, value in overrides.items():
            apply_override(spec, path, value)
        tag = "-".join(f"{p.rsplit('.', 1)[-1]}={v}" for p, v in overrides.items())
        spec.name = f"{base_spec.name}/{tag}" if tag else base_spec.name
        points.append((spec, overrides))
    return axes, points


def sweep_scenarios(
    base_spec: ScenarioSpec,
    grid: Dict[str, Sequence[Any]],
    verbose: bool = False,
    max_workers: Optional[int] = None,
) -> Dict[str, Any]:
    """Run the cartesian product of ``grid`` overrides on ``base_spec``.

    Returns a JSON-native merged payload::

        {"base": <base spec dict>,
         "grid": {path: [values...]},
         "runs": [{"name": ..., "overrides": {path: value},
                   "wall_time": ..., "result": RunResult.to_json()}]}

    Every point re-runs ``run_scenario`` on a deep copy of the base spec,
    so points are independent and the base spec is never mutated.
    ``max_workers > 1`` fans the points out over worker processes
    (ROADMAP: sweeps were sequential); ``runs`` keeps grid order either
    way, so sequential and parallel payloads are interchangeable.
    """
    axes, points = _grid_points(base_spec, grid)
    runs: List[Dict[str, Any]] = []
    if max_workers is not None and max_workers > 1:
        # an accelerator belongs to one process: only CPU workers can run
        # side by side (read from the environment, so that this parent
        # never takes the chip itself)
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            raise ValueError(
                f"sweep_scenarios(max_workers={max_workers}) runs grid points in "
                "worker processes, and only one process can hold an accelerator; "
                "set JAX_PLATFORMS=cpu for a parallel CPU sweep, or run the "
                "points in this process (max_workers=1)")
        # spawn (not fork): jax state must not be inherited mid-flight
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor

        ctx = mp.get_context("spawn")
        with ProcessPoolExecutor(max_workers=max_workers, mp_context=ctx) as ex:
            futs = [ex.submit(_sweep_worker, json.dumps(spec.to_dict())) for spec, _ in points]
            for (spec, overrides), fut in zip(points, futs):
                if verbose:
                    print(f"sweep: {spec.name}")
                runs.append({"name": spec.name, "overrides": overrides, **fut.result()})
    else:
        from repro.api.engine import run_scenario

        for spec, overrides in points:
            if verbose:
                print(f"sweep: {spec.name}")
            t0 = time.time()
            result = run_scenario(spec, verbose=verbose)
            runs.append(
                {
                    "name": spec.name,
                    "overrides": overrides,
                    "wall_time": time.time() - t0,
                    "result": result.to_json(),
                }
            )
    return {
        "base": base_spec.to_dict(),
        "grid": {a: list(grid[a]) for a in axes},
        "runs": runs,
    }
