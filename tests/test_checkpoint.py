"""CheckpointManager unit tests: manifest format-2 round-trip, crash-sim
atomicity, restore-time resharding through a plan, v1-format compat,
async-writer serialization, StepMonitor flagging, PreemptionGuard scoping,
and the step-indexed resume contract.  (Cross-plan/cross-extent elastic
restore runs on a real 8-device mesh in tests/_dist_checks.py.)"""
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.plan import build_plan
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.model import init_params
from repro.runtime import checkpoint as ckpt
from repro.runtime.resilience import PreemptionGuard, StepMonitor
from repro.train.optimizer import init_opt_state


def _plan(cfg):
    return build_plan(cfg, devices=jax.devices()[:1], impl="ref",
                      seq_len=64, global_batch=4)


def _state(cfg):
    params = init_params(cfg, jax.random.PRNGKey(0))
    return {"params": params, "opt": init_opt_state(params)}


# ---------------------------------------------------------------------------
# manifest format 2
# ---------------------------------------------------------------------------

def test_manifest_records_plan_and_bytes(tmp_path):
    cfg = get_reduced("qwen3-1.7b")
    plan, state = _plan(cfg), _state(cfg)
    mgr = ckpt.CheckpointManager(str(tmp_path), plan=plan)
    mgr.save(state, 3)
    man = mgr.manifest()
    assert man["format"] == ckpt.FORMAT == 2
    assert man["step"] == 3
    assert man["plan"]["dp"] == 1
    assert man["plan"]["zero_mode"] == plan.zero_mode
    assert man["plan"]["zero_extent"] == plan.mem["zero_extent"]
    # on one device every leaf saves whole: bytes/host == full state
    leaves = jax.tree.leaves(state)
    assert man["bytes_per_host"] == sum(np.asarray(x).nbytes
                                        for x in leaves)
    assert len(man["leaves"]) == len(leaves)
    for e in man["leaves"]:
        assert e["shards"] == 1 and e["dim"] is None

    got, step = mgr.restore(jax.tree.map(jnp.zeros_like, state))
    assert step == 3
    for a, b in zip(jax.tree.leaves(got), leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_leaf_files_roundtrip(tmp_path):
    """Per-shard layout on disk: a leaf split 4 ways writes 4 files, the
    manifest records (dim, shards), bytes/host counts one shard, and
    restore reassembles the leaf exactly."""
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    tree = {"x": x}
    paths, leaves, _ = ckpt._flatten_with_paths(tree)
    final = ckpt._write_checkpoint(str(tmp_path), 1, paths, leaves,
                                   [(0, 4)], {"dp": 4})
    shard_files = sorted(f for f in os.listdir(final) if f.endswith(".npy"))
    assert shard_files == [f"leaf_0.s{j}.npy" for j in range(4)]
    man = ckpt.read_manifest(str(tmp_path))
    assert man["leaves"][0] == {"path": paths[0], "shape": [8, 4],
                                "dtype": "float32", "dim": 0, "shards": 4}
    assert man["bytes_per_host"] == x.nbytes // 4
    got, step = ckpt.restore({"x": np.zeros_like(x)}, str(tmp_path))
    assert step == 1
    np.testing.assert_array_equal(got["x"], x)


def test_v1_whole_leaf_checkpoints_still_restore(tmp_path):
    """The seed layout (one leaf_<i>.npy per leaf, no format field) reads
    back through the same restore path."""
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.ones(3, np.float32)}
    paths, leaves, _ = ckpt._flatten_with_paths(tree)
    d = tmp_path / "step_00000007"
    d.mkdir()
    manifest = {"step": 7, "leaves": []}        # no "format": seed era
    for i, (p, x) in enumerate(zip(paths, leaves)):
        np.save(str(d / f"leaf_{i}.npy"), x)
        manifest["leaves"].append({"path": p, "shape": list(x.shape),
                                   "dtype": str(x.dtype)})
    with open(d / "manifest.json", "w") as f:
        json.dump(manifest, f)
    got, step = ckpt.restore(jax.tree.map(np.zeros_like, tree),
                             str(tmp_path))
    assert step == 7
    np.testing.assert_array_equal(got["w"], tree["w"])
    np.testing.assert_array_equal(got["b"], tree["b"])


# ---------------------------------------------------------------------------
# atomicity
# ---------------------------------------------------------------------------

def test_crash_mid_write_leaves_no_trace(tmp_path, monkeypatch):
    """A crash between shard files must leave neither a visible
    checkpoint nor a stale tmp dir (the writer cleans up and re-raises)."""
    state = {"a": np.zeros(4, np.float32), "b": np.ones(4, np.float32)}
    real_save, calls = np.save, []

    def boom(path, arr, **kw):
        calls.append(path)
        if len(calls) > 1:
            raise OSError("disk gone")
        real_save(path, arr, **kw)

    monkeypatch.setattr(np, "save", boom)
    with pytest.raises(OSError):
        ckpt.save(state, 5, str(tmp_path))
    assert len(calls) == 2                     # it really died mid-write
    assert ckpt.list_steps(str(tmp_path)) == []
    assert os.listdir(str(tmp_path)) == []     # tmp dir removed


# ---------------------------------------------------------------------------
# restore-time resharding
# ---------------------------------------------------------------------------

def test_restore_reshards_through_target_plan(tmp_path):
    """``manager.restore`` device_puts through the plan's
    ``state_shardings`` — every restored leaf is a committed device array
    matching the plan's layout, not host numpy."""
    cfg = get_reduced("qwen3-1.7b")
    plan, state = _plan(cfg), _state(cfg)
    mgr = ckpt.CheckpointManager(str(tmp_path), plan=plan)
    mgr.save(state, 1)
    got, _ = mgr.restore(jax.tree.map(jnp.zeros_like, state))
    sh = plan.state_shardings(state)
    for a, s in zip(jax.tree.leaves(got), jax.tree.leaves(
            sh, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))):
        assert isinstance(a, jax.Array)
        assert a.sharding.is_equivalent_to(s, a.ndim)
    # an explicit shardings pytree overrides the plan
    got2, _ = mgr.restore(jax.tree.map(jnp.zeros_like, state),
                          shardings=sh)
    for a, b in zip(jax.tree.leaves(got2), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# async writer serialization
# ---------------------------------------------------------------------------

def test_save_async_rapid_fire_serializes(tmp_path):
    """Back-to-back ``save_async`` calls never race on the directory:
    every step lands, no tmp dirs leak, and ``flush`` is idempotent."""
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=100)
    state = {"x": np.arange(4096, dtype=np.float32)}
    for s in range(1, 9):
        mgr.save_async(state, s)
    mgr.flush()
    assert mgr.list_steps() == list(range(1, 9))
    assert not [n for n in os.listdir(str(tmp_path)) if ".tmp" in n]
    mgr.flush()                                # no-op when idle
    assert mgr.latest_step() == 8


@pytest.mark.parametrize("steps", [(1, 2, 3), (1, 2, 3, 4)])
def test_save_async_gc_applies_keep(tmp_path, steps):
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=2)
    state = {"x": np.zeros(8, np.float32)}
    for s in steps:
        mgr.save_async(state, s)
    mgr.flush()
    assert mgr.list_steps() == list(steps[-2:])


# ---------------------------------------------------------------------------
# resilience plumbing
# ---------------------------------------------------------------------------

def test_step_monitor_flags_and_reports_outliers():
    mon = StepMonitor(window=50, threshold=1.5)
    for i in range(1, 11):
        mon.record(i, 0.1)
    mon.record(11, 0.5)
    assert mon.flagged
    step, dt, med = mon.flagged[-1]
    assert step == 11 and dt == 0.5 and abs(med - 0.1) < 1e-9
    assert mon.report()["stragglers"] == mon.flagged


def test_preemption_guard_install_is_scoped():
    """``install`` displaces the previous handler, ``uninstall`` puts it
    back — a guard never clobbers the process signal setup for good."""
    seen = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        g = PreemptionGuard()
        g.install()
        g.install()                            # idempotent
        signal.raise_signal(signal.SIGTERM)
        assert g.requested
        assert seen == []                      # ours, not the old handler
        g.uninstall()
        signal.raise_signal(signal.SIGTERM)
        assert seen == [signal.SIGTERM]        # old handler restored
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_iter_batches_resume_is_a_skip_not_a_replay():
    """``batch(step)`` keys on (seed, step) only, so iterating from a
    restore point yields exactly the uninterrupted run's batches."""
    cfg = DataConfig(vocab=101, seq_len=32, global_batch=4, cp=1,
                     zigzag=False)
    data = SyntheticLM(cfg)
    full = [b for _, b in data.iter_batches(0, 6)]
    resumed = list(data.iter_batches(4, 2))
    assert [s for s, _ in resumed] == [4, 5]
    for (_, b), ref in zip(resumed, full[4:]):
        for k in b:
            np.testing.assert_array_equal(b[k], ref[k])
