"""Plan-aware sharded checkpointing: async atomic saves, elastic restore.

``CheckpointManager`` is the one surface the trainer (and examples) talk
to.  Layout (format 2):  <dir>/step_<N>/

    manifest.json       — step, the saving plan, per-leaf sharding layout
    leaf_<i>.s<j>.npy   — shard j of leaf i, split along the leaf's
                          ZeRO-sharded dim

* **Sharded**: each leaf is split along the dim its ``NamedSharding``
  shards (the ExecutionPlan's hybrid-ZeRO layout), so on a fleet every
  host serializes only its addressable shards — bytes-per-host scale
  down with the ZeRO extent instead of every host dumping the full tree.
  The manifest records ``bytes_per_host`` (one shard per leaf) and the
  saving plan, so a restore knows what layout it is reading.
* **Atomic**: written to a unique ``step_<N>.tmp-<pid>-<n>`` dir then
  os.rename'd — a crash never leaves a half-checkpoint visible.
* **Async**: ``save_async`` snapshots device→host synchronously — the
  only part that must block training — and writes in a background
  writer thread.  The manager serializes writers (a second save joins
  the in-flight one) and ``flush`` is atexit-registered, so rapid-fire
  saves and interpreter teardown never race on a tmp dir.
* **Elastic**: ``restore`` reassembles shards and ``jax.device_put``s
  through the *target* plan's shardings — restoring a dp8×cp4 run on
  dp4×cp4 is a reshard at load time, not a migration.

The free functions (``save``/``restore``/``list_steps``/``latest_step``)
remain as the manager's building blocks.
"""
from __future__ import annotations

import atexit
import itertools
import json
import os
import shutil
import threading

import jax
import numpy as np

#: manifest format: 1 = whole-leaf files (seed), 2 = per-shard files
FORMAT = 2

_TMP_IDS = itertools.count()


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = [jax.tree_util.keystr(k) for k, _ in flat]
    leaves = [v for _, v in flat]
    return paths, leaves, treedef


def _shard_layout(shape, sharding) -> tuple[int | None, int]:
    """(dim, n_shards) the save layout splits this leaf on.

    Derived from the leaf's ``NamedSharding``: the first sharded dim
    whose mesh-axes extent divides it.  ``(None, 1)`` for replicated,
    unsharded, or plain-numpy leaves (they save whole).
    """
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is None or mesh is None:
        return None, 1
    for d, entry in enumerate(spec):
        if entry is None or d >= len(shape):
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        if n > 1 and shape[d] % n == 0:
            return d, n
    return None, 1


def _write_checkpoint(directory: str, step: int, paths, host_leaves,
                      layouts, plan_info: dict | None) -> str:
    """Write one checkpoint dir atomically; returns the final path."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = f"{final}.tmp-{os.getpid()}-{next(_TMP_IDS)}"
    os.makedirs(tmp)
    try:
        manifest = {"format": FORMAT, "step": step, "leaves": []}
        if plan_info:
            manifest["plan"] = plan_info
        bytes_host = 0
        for i, (p, x, (dim, n)) in enumerate(
                zip(paths, host_leaves, layouts)):
            x = np.asarray(x)
            shards = np.split(x, n, axis=dim) if n > 1 else [x]
            for j, s in enumerate(shards):
                np.save(os.path.join(tmp, f"leaf_{i}.s{j}.npy"), s)
            bytes_host += x.nbytes // n
            manifest["leaves"].append(
                {"path": p, "shape": list(x.shape), "dtype": str(x.dtype),
                 "dim": dim if n > 1 else None, "shards": n})
        manifest["bytes_per_host"] = bytes_host
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)   # never leave a tmp dir
        raise
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def save(tree, step: int, directory: str, plan=None):
    """Blocking atomic sharded save.  Returns the final checkpoint path.

    The shard layout comes from each leaf's own ``.sharding`` (device
    trees) — host-numpy trees save whole.  ``plan`` is recorded in the
    manifest when given.
    """
    paths, leaves, _ = _flatten_with_paths(tree)
    layouts = [_shard_layout(np.shape(x), getattr(x, "sharding", None))
               for x in leaves]
    host_leaves = jax.device_get(leaves)
    return _write_checkpoint(directory, step, paths, host_leaves, layouts,
                             _plan_info(plan))


def _plan_info(plan) -> dict | None:
    """The manifest's record of the saving plan (None when unplanned)."""
    if plan is None:
        return None
    pc = plan.pc
    return {"dp": pc.dp, "hp": pc.hp, "cp_outer": pc.cp_outer,
            "cp_inner": pc.cp_inner, "pods": pc.pods,
            "placement": pc.placement, "zero_mode": plan.zero_mode,
            "zero_extent": plan.mem.get("zero_extent", 1)}


class CheckpointManager:
    """Plan-aware checkpoint manager: the trainer's save/restore surface.

    ``save_async(state, step)`` snapshots device→host at the step
    boundary (the only blocking part) and writes per-shard files in a
    background writer thread; ``restore(state)`` reads any step back and
    reshards it through the *target* plan's shardings.  One writer is in
    flight at a time — overlapping saves join the previous write, and
    ``flush`` (atexit-registered) joins on exit.
    """

    def __init__(self, directory: str, plan=None, keep: int = 3):
        self.directory = directory
        self.plan = plan
        self.keep = keep
        self._writer: threading.Thread | None = None
        atexit.register(self.flush)

    # -- saving -------------------------------------------------------------

    def _snapshot(self, state):
        """Device→host snapshot + the per-leaf shard layout, read from
        the live arrays' shardings (falls back to whole-leaf for host
        trees)."""
        paths, leaves, _ = _flatten_with_paths(state)
        layouts = [_shard_layout(np.shape(x), getattr(x, "sharding", None))
                   for x in leaves]
        host_leaves = jax.device_get(leaves)       # blocking snapshot
        return paths, host_leaves, layouts

    def save(self, state, step: int) -> str:
        """Blocking sharded save (snapshot + write); returns the path."""
        self.flush()
        paths, host, layouts = self._snapshot(state)
        final = _write_checkpoint(self.directory, step, paths, host,
                                  layouts, _plan_info(self.plan))
        self._gc()
        return final

    def save_async(self, state, step: int):
        """Snapshot now, write in the background.

        Joins any write still in flight first, so two saves never race
        on the directory; the writer thread is non-daemon and ``flush``
        is atexit-registered, so teardown mid-write cannot truncate a
        checkpoint.
        """
        self.flush()
        paths, host, layouts = self._snapshot(state)
        info = _plan_info(self.plan)

        def _write():
            _write_checkpoint(self.directory, step, paths, host, layouts,
                              info)
            self._gc()

        self._writer = threading.Thread(target=_write,
                                        name=f"ckpt-write-{step}")
        self._writer.start()

    def flush(self):
        """Join the in-flight write; no-op when idle."""
        w, self._writer = self._writer, None
        if w is not None:
            w.join()

    def _gc(self):
        steps = self.list_steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)

    # -- restoring ----------------------------------------------------------

    def restore(self, template, *, step: int | None = None, plan=None,
                shardings=None):
        """Restore into ``template``'s structure, resharding through the
        target plan (``plan`` overrides the manager's; an explicit
        ``shardings`` pytree overrides both).  Returns ``(state, step)``.
        """
        self.flush()                   # a just-queued save is readable
        plan = plan or self.plan
        if shardings is None and plan is not None:
            shardings = plan.state_shardings(template)
        return restore(template, self.directory, step=step,
                       shardings=shardings)

    def list_steps(self):
        return list_steps(self.directory)

    def latest_step(self):
        return latest_step(self.directory)

    def manifest(self, step: int | None = None) -> dict:
        return read_manifest(self.directory, step)


def list_steps(directory: str):
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        if name.startswith("step_") and ".tmp" not in name:
            try:
                out.append(int(name[5:]))
            except ValueError:
                pass
    return sorted(out)


def latest_step(directory: str):
    steps = list_steps(directory)
    return steps[-1] if steps else None


def read_manifest(directory: str, step: int | None = None) -> dict:
    """The manifest of one checkpoint (latest when ``step`` is None)."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {directory}")
    path = os.path.join(directory, f"step_{step:08d}", "manifest.json")
    with open(path) as f:
        return json.load(f)


def _load_leaf(path: str, i: int, entry: dict, fmt: int) -> np.ndarray:
    if fmt < 2:                        # seed layout: one file per leaf
        return np.load(os.path.join(path, f"leaf_{i}.npy"))
    n = entry.get("shards", 1)
    parts = [np.load(os.path.join(path, f"leaf_{i}.s{j}.npy"))
             for j in range(n)]
    return parts[0] if n == 1 else np.concatenate(parts,
                                                  axis=entry["dim"])


def restore(template, directory: str, *, step: int | None = None,
            shardings=None):
    """Restore into ``template``'s structure.  Returns ``(tree, step)``.

    ``shardings``: optional pytree of NamedSharding — pass the *current*
    run's shardings to reshard elastically onto a different mesh; the
    shards are reassembled on host first, so the saved extent and the
    target extent are free to differ.
    """
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {directory}")
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    fmt = manifest.get("format", 1)
    paths, leaves, treedef = _flatten_with_paths(template)
    by_path = {e["path"]: i for i, e in enumerate(manifest["leaves"])}
    loaded = []
    for p, tmpl in zip(paths, leaves):
        i = by_path[p]
        x = _load_leaf(path, i, manifest["leaves"][i], fmt)
        assert list(x.shape) == list(tmpl.shape), (p, x.shape, tmpl.shape)
        loaded.append(x)
    tree = jax.tree_util.tree_unflatten(treedef, loaded)
    if shardings is not None:
        tree = jax.device_put(tree, shardings)
    return tree, step
