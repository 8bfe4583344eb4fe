"""ExecutionPlan: placement orderings, AMSP ZeRO selection, sub-group
fallback, describe(), and microbatched gradient accumulation."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_reduced
from repro.core.plan import build_plan, choose_zero_mode
from repro.core.topology import (AXIS_DATA, AXIS_HP, AXIS_INNER, AXIS_OUTER,
                                 ParallelConfig)
from repro.core.zero import leaf_extent, leaf_spec


class FakeDev:
    def __init__(self, i):
        self.id = i

    def __repr__(self):
        return f"d{self.id}"


def _fake_devs(n):
    return [FakeDev(i) for i in range(n)]


CFG = get_reduced("qwen3-1.7b")


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

def test_placement_minor_axis_orderings():
    """head_first: SeqAlltoAll (head) group gets consecutive device ids;
    context_first: the inner ring does — on a fake 16-device mesh."""
    pc_hf = ParallelConfig(hp=4, cp_outer=2, cp_inner=2,
                           placement="head_first")
    plan = build_plan(CFG, pc_hf, devices=_fake_devs(16))
    dev = plan.mesh.devices
    assert dev.shape == (1, 1, 4, 2, 2)
    assert [d.id for d in dev[0, 0, :, 0, 0]] == [0, 1, 2, 3]   # head minor
    assert [d.id for d in dev[0, 0, 0, 0, :]] == [0, 4]         # inner strided
    assert [d.id for d in dev[0, 0, 0, :, 0]] == [0, 8]         # outer strided

    pc_cf = ParallelConfig(hp=4, cp_outer=2, cp_inner=2,
                           placement="context_first")
    plan = build_plan(CFG, pc_cf, devices=_fake_devs(16))
    dev = plan.mesh.devices
    assert [d.id for d in dev[0, 0, 0, 0, :]] == [0, 1]         # inner minor
    assert [d.id for d in dev[0, 0, 0, :, 0]] == [0, 2]
    assert [d.id for d in dev[0, 0, :, 0, 0]] == [0, 4, 8, 12]  # head strided


def test_describe_reports_the_whole_plan():
    plan = build_plan(CFG, opt=None, devices=jax.devices()[:1],
                      grad_accum=2, seq_len=128, global_batch=8)
    s = plan.describe()
    for frag in ("placement=head_first", "grad_accum=2", "microbatch=4",
                 "remat", "zero", "leaf extents", "memory/dev"):
        assert frag in s, (frag, s)


# ---------------------------------------------------------------------------
# hybrid-ZeRO selection (AMSP) + sub-group fallback
# ---------------------------------------------------------------------------

def _fake_mesh(pc, n):
    from repro.core.topology import make_mesh
    return make_mesh(pc, devices=_fake_devs(n))


def test_zero_mode_from_memory_model():
    """The least-sharded AMSP mode whose param+opt state fits the budget
    wins (replica < dp < sp < dp×sp)."""
    mesh = _fake_mesh(ParallelConfig(dp=16, hp=8, cp_outer=1, cp_inner=2),
                      256)
    budget = 16e9
    # tiny model: replicate everywhere
    assert choose_zero_mode(int(1e6), mesh, budget)[0] == "replica"
    # 2B params: 28 GB of state; dp-wide (/16) fits
    assert choose_zero_mode(int(2e9), mesh, budget)[0] == "dp"
    # 100B params: only the full dp×sp extent (/256) fits
    assert choose_zero_mode(int(100e9), mesh, budget)[0] == "dp_sp"


def test_leaf_spec_subgroup_fallback():
    """A leaf whose dims don't divide the full group falls back to the
    largest divisible sub-group (dropping minor axes) — not to replica."""
    mesh = _fake_mesh(ParallelConfig(dp=4, hp=2), 8)
    group = (AXIS_DATA, AXIS_HP, AXIS_OUTER, AXIS_INNER)
    # divisible by the full 8-way group: shard 8-wide
    assert leaf_extent((16, 8), mesh, (group,), min_elems=1) == (8, group)
    # 12 % 8 != 0 but 12 % 4 == 0: falls back to (data,) 4-wide
    ext, axes = leaf_extent((12, 4), mesh, (group,), min_elems=1)
    assert (ext, axes) == (4, (AXIS_DATA,))
    spec = leaf_spec((12, 4), mesh, (group,), min_elems=1)
    assert spec == jax.sharding.PartitionSpec((AXIS_DATA,), None)
    # nothing divides: replicate
    assert leaf_extent((7, 5), mesh, (group,), min_elems=1) == (1, ())


def test_plan_leaf_extents_surface_fallbacks():
    """describe()/leaf_extents reports the extent per top-level leaf
    class under the chosen groups."""
    pc = ParallelConfig(dp=4, hp=2)
    plan = build_plan(CFG, pc, devices=_fake_devs(8), zero="dp_sp")
    ext = plan.leaf_extents()
    assert "embed" in ext and "blocks" in ext
    # the vocab=512 embedding divides the full 8-way group
    assert max(e for e, _ in ext["embed"]) == 8


# ---------------------------------------------------------------------------
# gradient accumulation
# ---------------------------------------------------------------------------

def _step_inputs(plan, seq=64, gb=8):
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import init_params
    from repro.train.optimizer import init_opt_state
    data = SyntheticLM(plan.data_config(seq, gb), plan.cfg)
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    params = init_params(plan.cfg, jax.random.PRNGKey(0))
    return params, init_opt_state(params), batch


def test_grad_accum_matches_large_batch():
    """grad_accum=4 on (4, 2, S) microbatches == one batch-8 step, in
    fp32, for params, opt state and metrics."""
    from repro.train.train_step import jit_train_step
    plan4 = build_plan(CFG, devices=jax.devices()[:1], grad_accum=4,
                       seq_len=64, global_batch=8)
    plan1 = build_plan(CFG, devices=jax.devices()[:1], grad_accum=1,
                       seq_len=64, global_batch=8)
    params, opt, batch4 = _step_inputs(plan4)
    assert batch4["tokens"].shape == (4, 2, 64)
    batch1 = {k: v.reshape((8,) + v.shape[2:]) for k, v in batch4.items()}

    with plan4.mesh:
        step4, _, _ = jit_train_step(plan4, params, donate=False)
        p4, o4, m4 = step4(params, opt, batch4)
    with plan1.mesh:
        step1, _, _ = jit_train_step(plan1, params, donate=False)
        p1, o1, m1 = step1(params, opt, batch1)

    np.testing.assert_allclose(float(m4["loss"]), float(m1["loss"]),
                               rtol=1e-6)
    assert float(m4["n_tokens"]) == float(m1["n_tokens"])
    for a, b in zip(jax.tree.leaves(p4), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(o4["m"]), jax.tree.leaves(o1["m"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)


def _walk_jaxprs(jaxpr):
    """Yield jaxpr and every nested sub-jaxpr (scan/remat/cond bodies)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                inner = getattr(u, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from _walk_jaxprs(inner)
                elif hasattr(u, "eqns"):
                    yield from _walk_jaxprs(u)


def _count_prim(jaxpr, name):
    return sum(1 for j in _walk_jaxprs(jaxpr) for e in j.eqns
               if e.primitive.name == name)


def test_grad_accum_single_reduction_point():
    """Structural jaxpr check: grads leave the microbatch scan as one
    carry, and the optimizer update (sqrt ops — the point where grads
    are reduced into the ZeRO-sharded AdamW state) runs once per step,
    outside the loop, not once per microbatch."""
    from repro.train.train_step import make_train_step

    def trace(accum):
        plan = build_plan(CFG, devices=jax.devices()[:1], grad_accum=accum,
                          seq_len=64, global_batch=8)
        params, opt, batch = _step_inputs(plan)
        return jax.make_jaxpr(make_train_step(plan))(params, opt, batch)

    j1, j4 = trace(1), trace(4)
    # the whole-program optimizer footprint must not scale with accum
    assert _count_prim(j4.jaxpr, "sqrt") == _count_prim(j1.jaxpr, "sqrt")

    outer_scans = [e for e in j4.jaxpr.eqns if e.primitive.name == "scan"
                   and e.params.get("length") == 4]
    assert len(outer_scans) == 1, \
        [e.primitive.name for e in j4.jaxpr.eqns]
    body = outer_scans[0].params["jaxpr"].jaxpr
    # no optimizer math inside the microbatch loop
    assert _count_prim(body, "sqrt") == 0
    # the scan carries exactly the grad tree: one leaf per param leaf
    from repro.models.model import init_params
    n_params = len(jax.tree.leaves(jax.eval_shape(
        lambda: init_params(CFG, jax.random.PRNGKey(0)))))
    assert outer_scans[0].params["num_carry"] == n_params


def test_batch_shardings_follow_accum_layout():
    plan = build_plan(CFG, devices=jax.devices()[:1], grad_accum=2,
                      seq_len=64, global_batch=8)
    sh = plan.batch_shardings("train")
    spec = sh["tokens"].spec
    assert spec[0] is None          # replicated accumulation axis
    flat = build_plan(CFG, devices=jax.devices()[:1], grad_accum=1,
                      seq_len=64, global_batch=8)
    assert len(flat.batch_shardings("train")["tokens"].spec) == 2


# ---------------------------------------------------------------------------
# FPDT chunk-offload memory model (device-free via _ShapeOnlyMesh)
# ---------------------------------------------------------------------------

def test_offload_split_conserves_bytes():
    from repro.core.plan import offload_resident_frac, offload_split
    assert offload_resident_frac(1) == 1.0
    assert offload_resident_frac(2) == 1.0       # both chunks resident
    assert offload_resident_frac(8) == 0.25      # active + prefetched of 8
    for chunks in (1, 2, 4, 8, 16):
        dev, host = offload_split(1e9, chunks)
        assert dev + host == 1e9                 # nothing double-counted
        assert dev == 1e9 * offload_resident_frac(chunks)
        assert host >= 0


def test_plan_memory_offload_trades_hbm_for_wire():
    from repro.core.plan import plan_memory
    pc = ParallelConfig(dp=1, hp=2, cp_outer=2, cp_inner=2)
    mems = {}
    for chunks in (1, 4, 8, 16):
        _, _, _, mem = plan_memory(CFG, pc, remat="none",
                                   memory_budget_gb=0.05, seq_len=8192,
                                   global_batch=8, offload_chunks=chunks)
        mems[chunks] = mem
    total = mems[1]["act_dev"]
    for a, b in ((1, 4), (4, 8), (8, 16)):
        assert mems[b]["act_dev"] < mems[a]["act_dev"]        # HBM freed …
        assert mems[b]["act_host"] > mems[a]["act_host"]      # … to host
        assert mems[b]["offload_wire_s"] > mems[a]["offload_wire_s"]
    for mem in mems.values():
        assert mem["act_dev"] + mem["act_host"] == total      # conserved
    assert mems[1]["offload_wire_s"] == 0.0
    # max trainable seq scales as 1/resident_frac = C/2 at a fixed budget
    base = mems[1]["max_seq_at_budget"]
    assert base > 0
    assert mems[8]["max_seq_at_budget"] >= 4 * base
    assert mems[16]["max_seq_at_budget"] >= 8 * base


def test_max_seq_at_budget_monotone_in_budget():
    from repro.core.plan import plan_memory
    pc = ParallelConfig(dp=1, hp=2, cp_outer=2, cp_inner=2)
    prev = -1
    for budget in (0.02, 0.05, 0.1, 0.5, 1.0):
        _, _, _, mem = plan_memory(CFG, pc, remat="none",
                                   memory_budget_gb=budget, seq_len=8192,
                                   global_batch=8, offload_chunks=8)
        assert mem["max_seq_at_budget"] >= prev, budget
        prev = mem["max_seq_at_budget"]
    assert prev > 0


def test_describe_reports_offload_line():
    plan = build_plan(CFG, devices=jax.devices()[:1], seq_len=128,
                      global_batch=8, offload_chunks=4)
    assert plan.offload_chunks == 4
    s = plan.describe()
    for frag in ("offload", "chunks=4", "max_seq@budget"):
        assert frag in s, (frag, s)
    # resident plans still print the line (chunks=1, no wire term)
    plan1 = build_plan(CFG, devices=jax.devices()[:1], seq_len=128,
                       global_batch=8)
    assert plan1.offload_chunks == 1
    assert "chunks=1" in plan1.describe()


def test_serve_spec_reuses_offload_accounting():
    """The serve memory model charges only the resident fraction of a KV
    block under offload — the same ``offload_split`` as training — so KV
    bytes are never double-counted device-side and the freed HBM shows up
    as a larger paged pool."""
    plan = build_plan(CFG, devices=jax.devices()[:1], seq_len=128,
                      global_batch=8, memory_budget_gb=0.05)
    kw = dict(max_seq_len=4096, max_batch=64)
    sv1 = plan.serve_spec(offload_chunks=1, **kw)
    sv8 = plan.serve_spec(offload_chunks=8, **kw)
    assert sv8.num_blocks > sv1.num_blocks    # freed HBM -> more pages fit
    # the logical per-token bytes are unchanged: only residency moved
    assert sv8.paged_bytes_per_token == sv1.paged_bytes_per_token
    assert sv8.max_blocks_per_seq == sv1.max_blocks_per_seq


@pytest.mark.parametrize("n,hp,want", [
    (4, 2, (2, 1, 2)),        # --hp 2 on a four-chip host: hp2 × cp2 (w=2)
    (4, None, (4, 1, 1)),     # config hp8 capped to the 4 chips
    (1, None, (1, 1, 1)),     # one chip
    (16, 2, (2, 2, 4)),       # cp8 = outer 2 × inner 4
])
def test_launcher_grid_from_device_count(n, hp, want):
    from repro.configs import get_parallel
    from repro.launch.train import device_grid
    pc = device_grid(get_parallel("qwen3-1.7b", "train_4k", False), n,
                     hp=hp)
    assert (pc.hp, pc.cp_outer, pc.cp_inner) == want
    assert pc.dp == 1 and pc.num_devices == n
