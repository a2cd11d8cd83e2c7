"""Checkpointing substrate (no orbax offline — built on numpy + JSON).

Layout per checkpoint step:
    <dir>/step_<n>/
        MANIFEST.json          # tree structure, dtypes, metadata
        arrays.npz             # one entry per leaf, keyed by tree path
    <dir>/history.jsonl        # append-only whole-run event sidecar
    <dir>/LATEST               # pointer at the newest complete step

Atomicity: pytrees are written to a ``.tmp`` directory then renamed;
STEP.json and LATEST land via write-fsync-rename. STEP.json existence IS
the step-completeness marker.

O(1) checkpoints — the history sidecar
--------------------------------------
The per-step payload holds only the engine's BOUNDED control state
(event queue, buffers, RNG streams, policy/incentive/controller state).
Everything that grows with run length — the sync round curves, the async
flush records and dispatch log — streams into ``history.jsonl``: one
JSON record per line, appended through ``append_history`` as the run
produces events.  Appends are buffered (no fsync per record); ``save``
fsyncs the sidecar FIRST and then commits the resulting byte offset
inside STEP.json (``history_offset``), which itself lands atomically.
A record is therefore durable exactly when some complete step's offset
covers it, and checkpoint write cost is O(events since the last save),
independent of total run length.

``begin`` is the engines' single resume/recovery entry point.  On
resume it restores the newest complete step, guards the writing engine
kind, TRUNCATES the sidecar back to the committed offset (discarding
partial lines or whole records from a killed run), and replays the
surviving records so the resumed run's result covers the whole history.
A step whose STEP.json lacks the ``engine`` or ``history_offset`` stamp
is refused (see docs/CHECKPOINTS.md).

Crash safety is tested by fault injection: every durable-write syscall
below routes through the module-level ``_os_write`` / ``_os_fsync`` /
``_os_replace`` / ``_os_rename`` indirections so the test harness
(tests/test_crash_injection.py) can fail or "kill" the process at each
individual write point without monkeypatching ``os`` globally.

Pytree paths are serialised as '/'-joined dict keys / list indices; restore
rebuilds the exact structure (dicts, lists, tuples) from the manifest, so no
template pytree is needed — but ``restore(like=...)`` is supported to cast
dtypes/shardings back onto a template.
"""
from __future__ import annotations

import json
import os
import shutil
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np

# Fault-injection seam: every durable write goes through these (see the
# module docstring). Production behaviour is byte-identical to calling
# the os functions directly.
_os_write = os.write
_os_fsync = os.fsync
_os_replace = os.replace
_os_rename = os.rename

HISTORY_FILE = "history.jsonl"


def _flatten(tree, prefix=""):
    """Yield (path, leaf) with structure markers for rebuilding."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _flatten(tree[k], f"{prefix}/{k}" if prefix else k)
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _flatten(v, f"{prefix}/{i}" if prefix else str(i))
    else:
        yield prefix, tree


def _structure(tree):
    if isinstance(tree, dict):
        return {"__kind__": "dict",
                "items": {k: _structure(v) for k, v in tree.items()}}
    if isinstance(tree, tuple):
        return {"__kind__": "tuple",
                "items": [_structure(v) for v in tree]}
    if isinstance(tree, list):
        return {"__kind__": "list",
                "items": [_structure(v) for v in tree]}
    return {"__kind__": "leaf"}


def _rebuild(struct, arrays, prefix=""):
    kind = struct["__kind__"]
    if kind == "dict":
        return {k: _rebuild(v, arrays, f"{prefix}/{k}" if prefix else k)
                for k, v in struct["items"].items()}
    if kind in ("list", "tuple"):
        seq = [_rebuild(v, arrays, f"{prefix}/{i}" if prefix else str(i))
               for i, v in enumerate(struct["items"])]
        return tuple(seq) if kind == "tuple" else seq
    return arrays[prefix]


def _write_file(path: str, data: bytes) -> None:
    """Write + fsync ``data`` to ``path`` through the injection seam."""
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    try:
        view = memoryview(data)
        while len(view):
            n = _os_write(fd, view)
            view = view[n:]
        _os_fsync(fd)
    finally:
        os.close(fd)


def save_pytree(path: str, tree, metadata: Optional[Dict[str, Any]] = None):
    """Atomic save of one pytree + metadata to ``path`` (a directory)."""
    tmp = path + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    host = jax.tree.map(lambda x: np.asarray(x), tree)
    arrays = dict(_flatten(host))
    # bf16 has no numpy dtype: view as uint16 and record the real dtype
    dtypes = {}
    packed = {}
    for k, v in arrays.items():
        v = np.asarray(v)
        dtypes[k] = str(v.dtype)
        if v.dtype.name == "bfloat16":
            packed[k] = v.view(np.uint16)
        else:
            packed[k] = v
    with open(os.path.join(tmp, "arrays.npz"), "wb") as f:
        np.savez(f, **{k.replace("/", "|"): v for k, v in packed.items()})
        f.flush()
        _os_fsync(f.fileno())
    manifest = {"structure": _structure(tree), "dtypes": dtypes,
                "metadata": metadata or {}}
    _write_file(os.path.join(tmp, "MANIFEST.json"),
                json.dumps(manifest).encode())
    if os.path.exists(path):
        shutil.rmtree(path)
    _os_rename(tmp, path)


def load_pytree(path: str, like=None):
    """Load a pytree saved by save_pytree. Returns (tree, metadata)."""
    with open(os.path.join(path, "MANIFEST.json")) as f:
        manifest = json.load(f)
    with np.load(os.path.join(path, "arrays.npz")) as z:
        arrays = {}
        for k in z.files:
            key = k.replace("|", "/")
            v = z[k]
            if manifest["dtypes"].get(key) == "bfloat16":
                import ml_dtypes
                v = v.view(ml_dtypes.bfloat16)
            arrays[key] = v
    tree = _rebuild(manifest["structure"], arrays)
    if like is not None:
        tree = jax.tree.map(
            lambda t, l: jax.numpy.asarray(t, getattr(l, "dtype", None)),
            tree, like)
    return tree, manifest["metadata"]


@dataclass
class ResumeState:
    """What ``CheckpointManager.begin`` hands a resuming engine: the
    restored step, per-task pytrees, the JSON-native coordinator payload,
    and the replayed sidecar records up to the committed offset."""

    step: int
    tasks: Dict[str, Any]
    coordinator: Dict[str, Any]
    history: List[dict]


class CheckpointManager:
    """Multi-task (MMFL) checkpoint manager with retention + LATEST and
    the append-only whole-run history sidecar (``history.jsonl``)."""

    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._hist_fd: Optional[int] = None
        self._hist_pos: Optional[int] = None

    # -- history sidecar ---------------------------------------------------

    @property
    def history_path(self) -> str:
        return os.path.join(self.dir, HISTORY_FILE)

    def _open_history(self) -> int:
        if self._hist_fd is None:
            self._hist_fd = os.open(
                self.history_path,
                os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
            self._hist_pos = os.fstat(self._hist_fd).st_size
        return self._hist_fd

    def append_history(self, record: dict) -> int:
        """Append one JSON record to the sidecar (buffered — NOT durable
        until the next ``save`` fsyncs and commits the offset). Returns
        the post-append byte offset. A crash mid-append leaves a partial
        line BEYOND every committed offset; resume truncates it away."""
        fd = self._open_history()
        data = (json.dumps(record, separators=(",", ":")) + "\n").encode()
        view = memoryview(data)
        while len(view):
            n = _os_write(fd, view)
            view = view[n:]
        assert self._hist_pos is not None
        self._hist_pos += len(data)
        return self._hist_pos

    def history_offset(self) -> int:
        """Byte length of the sidecar INCLUDING not-yet-committed
        appends (what the next ``save`` would commit)."""
        if self._hist_pos is not None:
            return self._hist_pos
        try:
            return os.path.getsize(self.history_path)
        except FileNotFoundError:
            return 0

    def read_history(self, upto: int) -> List[dict]:
        """Parse the committed record prefix: bytes [0, upto)."""
        if upto <= 0:
            return []
        try:
            with open(self.history_path, "rb") as f:
                data = f.read(upto)
        except FileNotFoundError:
            data = b""
        if len(data) < upto:
            raise ValueError(
                f"checkpoint sidecar {self.history_path!r} is shorter "
                f"({len(data)} bytes) than the committed offset {upto}: "
                "the sidecar was truncated or deleted after the step "
                "was written — the run's history cannot be recovered")
        return [json.loads(line) for line in data.splitlines() if line]

    def truncate_history(self, offset: int) -> None:
        """Drop every byte past ``offset`` — the recovery step: records
        (or partial lines) appended after the last completed ``save``
        were never committed, and a resumed run will re-produce them."""
        if self._hist_fd is not None:
            os.close(self._hist_fd)
            self._hist_fd = None
        self._hist_pos = None
        try:
            size = os.path.getsize(self.history_path)
        except FileNotFoundError:
            size = 0
            if offset > 0:
                raise ValueError(
                    f"checkpoint sidecar {self.history_path!r} is missing "
                    f"but step metadata committed offset {offset}")
        if size < offset:
            raise ValueError(
                f"checkpoint sidecar {self.history_path!r} is shorter "
                f"({size} bytes) than the committed offset {offset}")
        if size > offset:
            with open(self.history_path, "r+b") as f:
                f.truncate(offset)
                f.flush()
                _os_fsync(f.fileno())

    def close(self) -> None:
        if getattr(self, "_hist_fd", None) is not None:
            try:
                os.close(self._hist_fd)
            except OSError:
                pass
            self._hist_fd = None
            self._hist_pos = None

    def __del__(self):
        self.close()

    # -- steps -------------------------------------------------------------

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:08d}")

    def _write_atomic(self, path: str, data: bytes) -> None:
        tmp = path + ".tmp"
        _write_file(tmp, data)
        _os_replace(tmp, path)

    def save(self, step: int, tasks: Dict[str, Any],
             coordinator_state: Optional[Dict[str, Any]] = None,
             engine_kind: Optional[str] = None):
        """tasks: name -> pytree (e.g. {'params':..., 'opt':...}).

        With ``engine_kind`` set (every engine-driven save) the step is
        stamped with the writing engine and COMMITS the sidecar: the
        history fd is fsynced first, then the resulting byte offset
        lands inside STEP.json — so the records covered by a complete
        step are durable exactly when the step is."""
        sd = self._step_dir(step)
        for name, tree in tasks.items():
            save_pytree(os.path.join(sd, name.replace("/", "_")), tree,
                        metadata={"task": name, "step": step})
        meta = {"step": step, "tasks": sorted(tasks),
                "coordinator": coordinator_state or {}}
        if engine_kind is not None:
            meta["engine"] = engine_kind
            if self._hist_fd is not None:
                _os_fsync(self._hist_fd)
            meta["history_offset"] = self.history_offset()
        # STEP.json IS the step-completeness marker (latest_step's
        # fallback keys on its existence) and LATEST the newest pointer:
        # both land atomically via tmp + fsync + rename so a kill
        # mid-write can never leave a present-but-truncated marker
        self._write_atomic(os.path.join(sd, "STEP.json"),
                           json.dumps(meta).encode())
        self._write_atomic(os.path.join(self.dir, "LATEST"),
                           str(step).encode())
        self._gc()

    def _complete(self, step: int) -> bool:
        """STEP.json (written atomically, last) marks a step complete."""
        return os.path.exists(os.path.join(self._step_dir(step),
                                           "STEP.json"))

    def _step_meta(self, step: int) -> Dict[str, Any]:
        with open(os.path.join(self._step_dir(step), "STEP.json")) as f:
            return json.load(f)

    def latest_step(self) -> Optional[int]:
        """Newest COMPLETE step: the highest step directory that holds a
        STEP.json. The LATEST pointer is written for humans and external
        tools but deliberately NOT trusted here: ``save`` lands
        STEP.json (the completeness marker) BEFORE updating LATEST, so a
        kill in that window leaves the pointer one step stale — and it
        can equally be deleted, corrupt, or dangling at a hand-removed
        directory. Recovery must land on the HIGHEST complete step in
        every such case (tests/test_crash_injection.py sweeps each
        window), so the directory scan is the only authority."""
        for s in reversed(self.steps()):
            if self._complete(s):
                return s
        return None

    def restore(self, step: Optional[int] = None):
        """Returns (step, tasks dict, coordinator_state) or None."""
        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        meta = self._step_meta(step)
        tasks = {}
        for name in meta["tasks"]:
            tree, _ = load_pytree(
                os.path.join(self._step_dir(step), name.replace("/", "_")))
            tasks[name] = tree
        return step, tasks, meta.get("coordinator", {})

    def begin(self, engine_kind: str, resume: bool,
              clear_stale: bool = True) -> Optional[ResumeState]:
        """The engines' single resume/recovery entry point: decide
        between RESUMING from the newest complete step and STARTING
        FRESH in this directory.

        Returns a ``ResumeState`` when ``resume`` is set and a complete
        step exists — after guarding that the checkpoint was written by
        the SAME engine kind (resuming across kinds would silently
        retrain AND garbage-collect the foreign run's checkpoints, so it
        raises instead), truncating the sidecar back to the step's
        committed ``history_offset`` (recovery: records past the offset
        were never committed — a killed run's partial tail), and
        replaying the committed records (``history``). A step without
        the ``engine`` and ``history_offset`` stamps every engine save
        writes raises before anything in the directory changes.

        Returns ``None`` when starting fresh — after clearing any stale
        step directories and sidecar (``clear_stale``): ``_gc`` assumes
        monotonically increasing steps, so leftovers from an earlier run
        would collect the new run's first checkpoints, and a stale
        sidecar would prepend the OLD run's events to the new history.
        Safe even under ``resume=True``: reaching the fresh path means
        ``latest_step()`` found NO complete step, so anything present is
        partial junk from a killed save."""
        step = self.latest_step() if resume else None
        if step is not None:
            meta = self._step_meta(step)
            for key in ("engine", "history_offset"):
                if key not in meta:
                    raise ValueError(
                        f"cannot resume: checkpoint step {step} in "
                        f"{self.dir!r} has no {key!r} stamp in its "
                        "STEP.json; only steps written by an engine "
                        "save (the history sidecar layout) resume")
            saved = meta["engine"]
            if saved != engine_kind:
                if engine_kind == "async":
                    raise ValueError(
                        f"cannot resume: checkpoint step {step} in "
                        f"{self.dir!r} carries no async engine state (it "
                        "was written by a different engine); point the "
                        "async run at its own checkpoint directory")
                if saved == "async":
                    raise ValueError(
                        f"cannot resume: checkpoint step {step} in "
                        f"{self.dir!r} was written by the async engine; "
                        "resume it with mode='async' (or point this run "
                        "at its own checkpoint directory)")
                raise ValueError(
                    f"cannot resume: checkpoint step {step} in "
                    f"{self.dir!r} was written by engine kind {saved!r}, "
                    f"not {engine_kind!r}; point this run at its own "
                    "checkpoint directory")
            _, tasks, coord = self.restore(step)
            off = int(meta["history_offset"])
            self.truncate_history(off)
            return ResumeState(step, tasks, coord, self.read_history(off))
        if clear_stale and (self.steps()
                            or os.path.exists(self.history_path)):
            self.clear()
        return None

    def steps(self):
        out = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and not d.endswith(".tmp"):
                out.append(int(d.split("_")[1]))
        return sorted(out)

    def clear(self):
        """Remove every step, LATEST, and the history sidecar. A fresh
        (non-resume) run starting over in a previously-used directory
        must call this before its first save: ``_gc`` assumes
        monotonically increasing step numbers, so a stale HIGHER-numbered
        step from the earlier run would get the new run's first
        checkpoint garbage-collected and leave LATEST dangling at a
        deleted step — and a stale sidecar would prepend the old run's
        records to the new history."""
        self.close()
        latest = os.path.join(self.dir, "LATEST")
        if os.path.exists(latest):
            os.remove(latest)     # first, so a kill mid-clear can never
        for s in self.steps():    # leave LATEST pointing at a gone step
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
        if os.path.exists(self.history_path):
            os.remove(self.history_path)

    def _gc(self):
        steps = self.steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
