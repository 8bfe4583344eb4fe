"""Distributed equivalence checks, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (device count is locked
at first jax import, so these cannot run inside the main pytest process).

Usage:  python tests/_dist_checks.py <check-name>
Prints ``PASS <name>`` on success; any assertion raises.
"""
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np          # noqa: E402
import jax                  # noqa: E402
import jax.numpy as jnp     # noqa: E402


def err(a, b):
    return float(np.abs(np.asarray(a, np.float64)
                        - np.asarray(b, np.float64)).max())


def _runtimes(pc):
    from repro.core.runtime import Runtime
    from repro.core.topology import ParallelConfig, make_mesh
    mesh = make_mesh(pc)
    rt = Runtime(mesh=mesh, pc=pc, impl="ref")
    pc0 = ParallelConfig()
    mesh0 = make_mesh(pc0, devices=jax.devices()[:1])
    rt0 = Runtime(mesh=mesh0, pc=pc0, impl="ref")
    return rt, rt0


def check_attention_grid():
    from repro.core.topology import ParallelConfig, make_mesh
    from repro.core.attention2d import Attn2DConfig, attention_2d
    from repro.core.zigzag import to_zigzag, from_zigzag
    from repro.kernels.ref import attention_ref

    rng = np.random.default_rng(1)
    B, S, H, HKV, D = 2, 64, 8, 4, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    def oracle(q, k, v):
        out, _ = attention_ref(q, k, v, causal=True)
        return (out * w).sum(), out

    (_, o_ref), g_ref = jax.value_and_grad(
        oracle, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    grids = [(1, 1, 1, 4, "head_first"), (1, 1, 2, 2, "context_first"),
             (1, 2, 2, 2, "head_first"), (1, 4, 1, 2, "head_first"),
             (1, 8, 1, 1, "head_first"), (2, 2, 2, 1, "context_first"),
             (2, 1, 1, 2, "head_first")]
    for dp, hp, no, wi, placement in grids:
        pc = ParallelConfig(dp=dp, hp=hp, cp_outer=no, cp_inner=wi,
                            placement=placement)
        mesh = make_mesh(pc)
        cp = pc.cp
        cfg = Attn2DConfig(hp=hp, n_out=no, w=wi, causal=True, impl="ref")

        def dist(q, k, v):
            qz, kz, vz = (to_zigzag(x, cp) for x in (q, k, v))
            with mesh:
                out = attention_2d(qz, kz, vz, mesh=mesh, cfg=cfg)
            out = from_zigzag(out, cp)
            return (out * w).sum(), out

        with mesh:
            (_, o_d), g_d = jax.value_and_grad(
                dist, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        assert err(o_d, o_ref) < 5e-6, (hp, no, wi, err(o_d, o_ref))
        for a, b in zip(g_d, g_ref):
            assert err(a, b) < 5e-6, (hp, no, wi)
    print("PASS attention_grid")


def check_attention_modes():
    from repro.core.topology import ParallelConfig, make_mesh
    from repro.core.attention2d import Attn2DConfig, attention_2d
    from repro.core.zigzag import to_zigzag, from_zigzag
    from repro.kernels.ref import attention_ref

    rng = np.random.default_rng(2)
    B, S, H, HKV, D = 1, 96, 4, 2, 8
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    cases = [
        dict(causal=True, zigzag=True, window=20, softcap=0.0,
             hp=2, no=2, wi=2),
        dict(causal=True, zigzag=True, window=None, softcap=25.0,
             hp=2, no=1, wi=2),
        dict(causal=False, zigzag=False, window=None, softcap=0.0,
             hp=2, no=2, wi=2),
        dict(causal=True, zigzag=False, window=None, softcap=0.0,
             hp=1, no=2, wi=2),
        dict(causal=True, zigzag=False, window=12, softcap=0.0,
             hp=2, no=1, wi=2),
    ]
    for c in cases:
        cp = c["no"] * c["wi"]
        pc = ParallelConfig(hp=c["hp"], cp_outer=c["no"], cp_inner=c["wi"])
        mesh = make_mesh(pc)
        cfg = Attn2DConfig(hp=c["hp"], n_out=c["no"], w=c["wi"],
                           causal=c["causal"], zigzag=c["zigzag"],
                           window=c["window"], softcap=c["softcap"],
                           impl="ref")
        zz = c["zigzag"] and c["causal"]

        def oracle(q, k, v):
            out, _ = attention_ref(q, k, v, causal=c["causal"],
                                   window=c["window"], softcap=c["softcap"])
            return (out * w).sum(), out

        def dist(q, k, v):
            if zz:
                q, k, v = (to_zigzag(x, cp) for x in (q, k, v))
            with mesh:
                out = attention_2d(q, k, v, mesh=mesh, cfg=cfg)
            return ((from_zigzag(out, cp) if zz else out) * w).sum(), \
                from_zigzag(out, cp) if zz else out

        (_, o_ref), g_ref = jax.value_and_grad(
            oracle, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        with mesh:
            (_, o_d), g_d = jax.value_and_grad(
                dist, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        assert err(o_d, o_ref) < 5e-6, (c, err(o_d, o_ref))
        for a, b in zip(g_d, g_ref):
            assert err(a, b) < 5e-6, c
    print("PASS attention_modes")


def check_ring_pallas_path():
    """Double-ring 2D-Attention on ``impl="pallas_interpret"``: the traced
    (axis_index-derived) band offsets must stay on the Pallas kernels — the
    jnp fallbacks are poisoned to prove no silent flashref downgrade — and
    out + grads must match the single-device oracle."""
    from repro.core.topology import ParallelConfig, make_mesh
    from repro.core.attention2d import Attn2DConfig, attention_2d
    from repro.core.zigzag import to_zigzag, from_zigzag
    from repro.kernels import ref as ref_mod
    from repro.kernels.ref import attention_ref

    rng = np.random.default_rng(3)
    B, S, H, HKV, D = 1, 64, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    cases = [dict(window=None, softcap=0.0),
             dict(window=12, softcap=20.0)]
    pc = ParallelConfig(dp=1, hp=2, cp_outer=2, cp_inner=2)
    mesh = make_mesh(pc)
    cp = pc.cp

    def boom(*a, **kw):
        raise AssertionError("jnp fallback selected on the ring path")

    poisoned = ("attention_ref_chunked", "attention_bwd_ref_chunked")
    saved = {n: getattr(ref_mod, n) for n in poisoned}
    for case in cases:
        def oracle(q, k, v):
            out, _ = attention_ref(q, k, v, causal=True, **case)
            return (out * w).sum(), out

        (_, o_ref), g_ref = jax.value_and_grad(
            oracle, argnums=(0, 1, 2), has_aux=True)(q, k, v)

        cfg = Attn2DConfig(hp=2, n_out=2, w=2, causal=True,
                           impl="pallas_interpret", **case)

        def dist(q, k, v):
            qz, kz, vz = (to_zigzag(x, cp) for x in (q, k, v))
            with mesh:
                out = attention_2d(qz, kz, vz, mesh=mesh, cfg=cfg)
            out = from_zigzag(out, cp)
            return (out * w).sum(), out

        for n in poisoned:
            setattr(ref_mod, n, boom)
        try:
            with mesh:
                (_, o_d), g_d = jax.value_and_grad(
                    dist, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        finally:
            for n, fn in saved.items():
                setattr(ref_mod, n, fn)
        assert err(o_d, o_ref) < 5e-5, (case, err(o_d, o_ref))
        for a, b in zip(g_d, g_ref):
            assert err(a, b) < 5e-5, case
    print("PASS ring_pallas_path")


def check_exchange_scopes():
    """hp2 x cp2 on four devices, fwd+bwd compiled: every all-to-all
    carries the ``ulysses_a2a`` scope in its ``op_name`` and every
    collective-permute the ``ring`` scope (runtime/spans.py), so that a
    trace of the four-chip grid can split the exchange by its kind."""
    import re
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.topology import (BATCH_AXES, SEQ_AXES, ParallelConfig,
                                     make_mesh)
    from repro.core.attention2d import Attn2DConfig, attention_2d
    from repro.runtime import spans

    pc = ParallelConfig(dp=1, hp=2, cp_outer=1, cp_inner=2)
    mesh = make_mesh(pc, devices=jax.devices()[:4])
    cfg = Attn2DConfig(hp=2, n_out=1, w=2, causal=True, impl="ref")
    sh = NamedSharding(mesh, P(BATCH_AXES, SEQ_AXES, None, None))
    q = jax.ShapeDtypeStruct((1, 64, 4, 16), jnp.float32, sharding=sh)
    kv = jax.ShapeDtypeStruct((1, 64, 2, 16), jnp.float32, sharding=sh)

    def loss(q, k, v):
        return attention_2d(q, k, v, mesh=mesh, cfg=cfg).sum()

    with mesh:
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile().as_text()
    seen = {}
    for line in hlo.splitlines():
        m = re.search(r"= [^=]*? (all-to-all|collective-permute)"
                      r"(?:-start)?\(", line)
        if not m:
            continue
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        want = (spans.ULYSSES_A2A if m.group(1) == "all-to-all"
                else spans.RING)
        assert re.search(rf"(^|[/(]){want}([/)]|$)", name), (m.group(1),
                                                              name)
        seen[m.group(1)] = seen.get(m.group(1), 0) + 1
    assert seen.get("all-to-all") and seen.get("collective-permute"), seen
    print("PASS exchange_scopes")


def kernel_calls(jaxpr) -> list:
    """Names of the ``pallas_call``s in ``jaxpr`` and every jaxpr nested in
    it (scan, checkpoint, shard_map and custom_vjp bodies), one per call
    site: a scan body counts once, whatever its length."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for v in eqn.params.values():
            for u in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(u, "jaxpr", u)
                if hasattr(inner, "eqns"):
                    names += kernel_calls(inner)
    return names


def check_scpp_ring_saves_kernel_residuals():
    """Selective Checkpoint++ on the hp2 x cp2 grid (one inner ring of
    w = 2): the grad of two checkpointed attention layers holds the ring's
    w forward kernels once, not again in the backward's recompute (2w under
    ``full``), and its grads equal those of no remat."""
    from repro.core.topology import ParallelConfig, make_mesh
    from repro.core.attention2d import Attn2DConfig, attention_2d
    from repro.models.model import _scan_blocks, remat_policy
    from repro.runtime import spans

    pc = ParallelConfig(dp=1, hp=2, cp_outer=1, cp_inner=2)
    mesh = make_mesh(pc, devices=jax.devices()[:4])
    cfg = Attn2DConfig(hp=2, n_out=1, w=2, causal=True,
                       impl="pallas_interpret")
    B, S, H, D, L = 1, 64, 4, 16, 2
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((B, S, H * D)), jnp.float32)
    params = {n: jnp.asarray(0.2 * rng.standard_normal((L, H * D, H * D)),
                             jnp.float32) for n in ("wq", "wk", "wv", "wo")}

    def body(x, lp):
        q, k, v = ((x @ lp[n]).reshape(B, S, H, D) for n in ("wq", "wk",
                                                              "wv"))
        o = attention_2d(q, k, v, mesh=mesh, cfg=cfg)
        return x + o.reshape(B, S, H * D) @ lp["wo"], jnp.zeros(())

    def grad(remat):
        def loss(p):
            return (_scan_blocks(body, x, p, remat_policy(remat))[0]
                    ** 2).sum()
        return jax.grad(loss)

    grads = {}
    with mesh:
        for remat, n_fwd in (("scpp", cfg.w), ("full", 2 * cfg.w),
                             ("none", cfg.w)):
            names = kernel_calls(jax.make_jaxpr(grad(remat))(params).jaxpr)
            assert names.count(spans.FLASH_FWD) == n_fwd, (remat, names)
            grads[remat] = grad(remat)(params)
    for n in params:
        assert err(grads["scpp"][n], grads["none"][n]) < 1e-5, n
    print("PASS scpp_ring_saves_kernel_residuals")


def check_ssm():
    from repro.core.topology import ParallelConfig
    from repro.models.ssm import (Mamba1Dims, Mamba2Dims, init_mamba1,
                                  init_mamba2, mamba1_apply, mamba2_apply)
    pc = ParallelConfig(dp=1, hp=2, cp_outer=2, cp_inner=2)
    rt, rt0 = _runtimes(pc)
    key = jax.random.PRNGKey(0)
    B, S, D = 2, 64, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)

    m1 = Mamba1Dims(d_model=D, d_inner=2 * D, d_state=8, seg=8)
    p1 = init_mamba1(key, m1)
    y_d = mamba1_apply(p1, x, rt, m1)
    y_s = mamba1_apply(p1, x, rt0, m1)
    assert err(y_d, y_s) < 1e-5
    g_d = jax.grad(lambda x: (mamba1_apply(p1, x, rt, m1) ** 2).sum())(x)
    g_s = jax.grad(lambda x: (mamba1_apply(p1, x, rt0, m1) ** 2).sum())(x)
    assert err(g_d, g_s) < 1e-5

    m2 = Mamba2Dims(d_model=D, d_inner=2 * D, d_state=8, head_dim=8, seg=8)
    p2 = init_mamba2(key, m2)
    y_d = mamba2_apply(p2, x, rt, m2)
    y_s = mamba2_apply(p2, x, rt0, m2)
    assert err(y_d, y_s) < 5e-5
    print("PASS ssm")


def check_moe():
    from repro.core.topology import ParallelConfig
    from repro.models.moe import MoEDims, init_moe, moe_apply
    pc = ParallelConfig(dp=2, hp=2, cp_outer=1, cp_inner=2)
    rt, rt0 = _runtimes(pc)
    B, S, D = 2, 32, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)
    m = MoEDims(d_model=D, n_experts=16, top_k=2, d_ff=32, n_shared=1,
                capacity_factor=8.0)
    p = init_moe(jax.random.PRNGKey(0), m)
    y1, _ = moe_apply(p, x, rt, m)
    y0, _ = moe_apply(p, x, rt0, m)
    assert err(y1, y0) < 5e-6
    g1 = jax.grad(lambda p: (moe_apply(p, x, rt, m)[0] ** 2).sum())(p)
    g0 = jax.grad(lambda p: (moe_apply(p, x, rt0, m)[0] ** 2).sum())(p)
    for kk in ("router", "w1", "w2", "w3"):
        assert err(g1[kk], g0[kk]) < 1e-4, kk
    print("PASS moe")


def check_e2e_loss():
    """Full forward_loss on an 8-device 2D mesh == single device, for one
    arch per family (incl. zigzag data layout handling)."""
    from repro.configs import get_reduced
    from repro.core.topology import ParallelConfig
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models.model import forward_loss, init_params

    for name, grid in [("qwen3-1.7b", (1, 2, 2, 2)),
                       ("gemma2-2b", (2, 2, 1, 2)),
                       ("zamba2-7b", (1, 4, 1, 2)),
                       ("falcon-mamba-7b", (1, 1, 4, 2)),
                       ("deepseek-v2-lite-16b", (1, 4, 2, 1)),
                       ("whisper-small", (1, 4, 1, 2))]:
        dp, hp, no, wi = grid
        cfg = get_reduced(name)
        pc = ParallelConfig(dp=dp, hp=hp, cp_outer=no, cp_inner=wi)
        rt, rt0 = _runtimes(pc)
        params = init_params(cfg, jax.random.PRNGKey(0))
        zz = cfg.zigzag and cfg.family in ("dense", "moe", "encdec")
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64,
                                      global_batch=2, cp=pc.cp, zigzag=zz),
                           cfg)
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        data0 = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64,
                                       global_batch=2, cp=1, zigzag=False),
                            cfg)
        batch0 = {k: jnp.asarray(v) for k, v in data0.batch(0).items()}
        with rt.mesh:
            loss_d, _ = forward_loss(params, batch, rt, cfg)
        with rt0.mesh:
            loss_s, _ = forward_loss(params, batch0, rt0, cfg)
        assert abs(float(loss_d) - float(loss_s)) < 1e-3, \
            (name, float(loss_d), float(loss_s))
    print("PASS e2e_loss")


def check_decode_consistency():
    """Distributed prefill + decode == the same logits as single-device."""
    from repro.configs import get_reduced
    from repro.core.topology import ParallelConfig
    from repro.models.decode import decode_step, grow_caches, prefill
    from repro.models.model import init_params

    for name, grid in [("qwen3-1.7b", (1, 2, 2, 1)),
                       ("gemma2-2b", (1, 2, 1, 2)),
                       ("deepseek-v2-lite-16b", (1, 4, 1, 1)),
                       ("falcon-mamba-7b", (1, 1, 2, 2))]:
        dp, hp, no, wi = grid
        cfg = get_reduced(name)
        pc = ParallelConfig(dp=dp, hp=hp, cp_outer=no, cp_inner=wi)
        rt, rt0 = _runtimes(pc)
        params = init_params(cfg, jax.random.PRNGKey(0))
        B, S = 2, 32
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens}
        if cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                jax.random.PRNGKey(2), (B, cfg.enc_frames, cfg.d_model))
        with rt.mesh:
            lg_d, caches_d = prefill(params, batch, rt, cfg)
            caches_d = grow_caches(cfg, caches_d, 4)
            nxt = np.asarray(
                jnp.argmax(lg_d[:, -1], axis=-1))[:, None].astype(np.int32)
            lg2_d, _ = decode_step(params, caches_d, jnp.asarray(nxt),
                                   jnp.int32(S), rt, cfg)
        with rt0.mesh:
            lg_s, caches_s = prefill(params, batch, rt0, cfg)
            caches_s = grow_caches(cfg, caches_s, 4)
            lg2_s, _ = decode_step(params, caches_s, jnp.asarray(nxt),
                                   jnp.int32(S), rt0, cfg)
        assert err(lg_d, lg_s) < 1e-3, (name, err(lg_d, lg_s))
        assert err(lg2_d, lg2_s) < 1e-3, (name, err(lg2_d, lg2_s))
    print("PASS decode_consistency")


def check_plan_placement():
    """ExecutionPlan round-trips head_first AND context_first through
    attention_2d with identical numerics (vs the single-device oracle):
    placement only permutes device placement, never the math."""
    from repro.configs import get_reduced
    from repro.core.plan import build_plan
    from repro.core.topology import ParallelConfig
    from repro.core.zigzag import to_zigzag, from_zigzag
    from repro.core.attention2d import attention_2d
    from repro.kernels.ref import attention_ref

    rng = np.random.default_rng(7)
    B, S, H, HKV, D = 1, 64, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    o_ref, _ = attention_ref(q, k, v, causal=True)

    outs = {}
    for placement in ("head_first", "context_first"):
        pc = ParallelConfig(hp=2, cp_outer=2, cp_inner=2,
                            placement=placement)
        plan = build_plan(get_reduced("qwen3-1.7b"), pc, impl="ref")
        cfg2d = plan.attn2d(causal=True, zigzag=True)
        assert (cfg2d.hp, cfg2d.n_out, cfg2d.w) == (2, 2, 2)
        qz, kz, vz = (to_zigzag(x, pc.cp) for x in (q, k, v))
        with plan.mesh:
            out = attention_2d(qz, kz, vz, mesh=plan.mesh, cfg=cfg2d)
        outs[placement] = np.asarray(from_zigzag(out, pc.cp))
        assert err(outs[placement], o_ref) < 5e-6, placement
    assert err(outs["head_first"], outs["context_first"]) == 0.0
    print("PASS plan_placement")


def check_accum_collectives():
    """Gradient accumulation on a dp=2 mesh: (a) the partitioned HLO's
    collective instruction count does not scale with grad_accum (the
    grad reduction/update point is outside the microbatch loop — no
    per-microbatch resharding or optimizer application), and (b) the
    sharded accum=2 step matches the single-device flat step."""
    import re
    from repro.configs import get_reduced
    from repro.core.plan import build_plan
    from repro.core.topology import ParallelConfig
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import init_params
    from repro.train.optimizer import init_opt_state
    from repro.train.train_step import jit_train_step, make_train_step

    cfg = get_reduced("qwen3-1.7b")

    def compile_counts(accum):
        plan = build_plan(cfg, ParallelConfig(dp=2), grad_accum=accum,
                          seq_len=64, global_batch=8, zero="dp",
                          impl="ref")
        p = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        o = jax.eval_shape(init_opt_state, p)
        p_sh = plan.param_shardings(p)
        shp = (accum, 8 // accum, 64) if accum > 1 else (8, 64)
        batch = {kk: jax.ShapeDtypeStruct(shp, jnp.int32)
                 for kk in ("tokens", "labels", "positions")}
        with plan.mesh:
            fn = jax.jit(make_train_step(plan),
                         in_shardings=(p_sh, plan.opt_shardings(p_sh),
                                       plan.batch_shardings("train")),
                         out_shardings=(p_sh, plan.opt_shardings(p_sh),
                                        None))
            hlo = fn.lower(p, o, batch).compile().as_text()
        return {op: len(re.findall(op + r"[-.\d]*\(", hlo))
                for op in ("all-reduce", "reduce-scatter")}

    c1, c4 = compile_counts(1), compile_counts(4)
    assert c1 == c4, (c1, c4)

    # numerics: dp=2 × accum=2 == single-device flat batch
    results = {}
    for tag, pc, accum in (("dist", ParallelConfig(dp=2), 2),
                           ("single", ParallelConfig(), 1)):
        devs = None if pc.dp > 1 else jax.devices()[:1]
        plan = build_plan(cfg, pc, devices=devs, grad_accum=accum,
                          seq_len=64, global_batch=8, impl="ref")
        data = SyntheticLM(plan.data_config(64, 8), cfg)
        batch = {kk: jnp.asarray(vv) for kk, vv in data.batch(0).items()}
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = init_opt_state(params)
        with plan.mesh:
            step, _, _ = jit_train_step(plan, params, donate=False)
            p2, _, m = step(params, opt, batch)
        results[tag] = (jax.device_get(p2), float(m["loss"]))
    assert abs(results["dist"][1] - results["single"][1]) < 1e-5
    for a, b in zip(jax.tree.leaves(results["dist"][0]),
                    jax.tree.leaves(results["single"][0])):
        assert err(a, b) < 1e-5
    print("PASS accum_collectives")


def check_packed_parity():
    """Packed-document training parity: one packed batch of K documents
    must produce the same loss and parameter gradients as K independent
    unpacked runs (token-weighted aggregate), on a ring (cp>1) config and
    a Ulysses (hp>1) config — and the packed traced step must stay on the
    Pallas kernels (the jnp fallbacks are poisoned: no flashref
    downgrade for the doc-masked path)."""
    import dataclasses as dc
    from repro.configs import get_reduced
    from repro.core.plan import build_plan
    from repro.core.topology import ParallelConfig
    from repro.data.pipeline import PackedLM
    from repro.kernels import ref as ref_mod
    from repro.models.model import forward_loss, init_params

    cfg = dc.replace(get_reduced("qwen3-1.7b"), window=None,
                     window_pattern=0)
    S, B = 64, 2
    params = init_params(cfg, jax.random.PRNGKey(0))

    def boom(*a, **kw):
        raise AssertionError("jnp fallback selected on the packed path")

    poisoned = ("attention_ref_chunked", "attention_bwd_ref_chunked")
    saved = {n: getattr(ref_mod, n) for n in poisoned}

    # single-device per-document oracle (token-weighted aggregation)
    plan0 = build_plan(cfg, ParallelConfig(), devices=jax.devices()[:1],
                       impl="ref", seq_len=S, global_batch=B)

    for pc in (ParallelConfig(dp=1, hp=1, cp_outer=2, cp_inner=2),
               ParallelConfig(dp=1, hp=2, cp_outer=1, cp_inner=1),
               # the full 2D composition: head AlltoAll gathers the doc
               # table, the zigzag ring keeps it stationary
               ParallelConfig(dp=1, hp=2, cp_outer=1, cp_inner=2)):
        plan = build_plan(cfg, pc, impl="pallas_interpret", seq_len=S,
                          global_batch=B, packed=True, mean_doc_len=16)
        data = PackedLM(plan.data_config(S, B, doc_len_range=(10, 38)),
                        cfg)
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        grad_of = jax.value_and_grad(
            lambda p, b, rt: forward_loss(p, b, rt, cfg)[0],
            has_aux=False)
        for n in poisoned:
            setattr(ref_mod, n, boom)
        try:
            with plan.mesh:
                loss_p, grads_p = grad_of(params, batch, plan.rt)
        finally:
            for n, fn in saved.items():
                setattr(ref_mod, n, fn)

        # K independent unpacked runs, one per document
        total, loss_acc = 0.0, 0.0
        grad_acc = jax.tree.map(lambda x: np.zeros(x.shape, np.float64),
                                params)
        docs = [d for seq_docs in data.documents(0) for d in seq_docs]
        assert len(docs) >= 3, len(docs)
        with plan0.mesh:
            for d in docs:
                db = {k: jnp.asarray(d[k][None]) for k in
                      ("tokens", "labels", "positions")}
                loss_d, grads_d = grad_of(params, db, plan0.rt)
                n_d = float((d["labels"] >= 0).sum())
                total += n_d
                loss_acc += n_d * float(loss_d)
                grad_acc = jax.tree.map(
                    lambda a, g: a + n_d * np.asarray(g, np.float64),
                    grad_acc, grads_d)
        loss_ind = loss_acc / total
        grads_ind = jax.tree.map(lambda a: a / total, grad_acc)

        assert abs(float(loss_p) - loss_ind) < 1e-5, \
            (pc, float(loss_p), loss_ind)
        for a, b in zip(jax.tree.leaves(grads_p),
                        jax.tree.leaves(grads_ind)):
            assert err(a, b) < 1e-5, pc
    print("PASS packed_parity")


def check_grad_compression():
    """int8 error-feedback psum inside shard_map over the data axis."""
    from jax.sharding import PartitionSpec as P
    from repro.core.topology import ParallelConfig, make_mesh, AXIS_DATA
    from repro.train.optimizer import compressed_psum

    pc = ParallelConfig(dp=8)
    mesh = make_mesh(pc)
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)
    err_state = jnp.zeros((8, 64), jnp.float32)

    def local(g, e):
        s, e2 = compressed_psum(g, e, AXIS_DATA)
        return s, e2

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(AXIS_DATA, None), P(AXIS_DATA, None)),
                      out_specs=(P(None, None), P(AXIS_DATA, None)),
                      check_vma=False)
    # accumulate over steps: error feedback should keep the running sum
    # close to the exact running sum
    exact_acc = np.zeros((1, 64))
    comp_acc = np.zeros((1, 64))
    e = err_state
    for step in range(20):
        g_step = jax.random.normal(jax.random.PRNGKey(step), (8, 64))
        with mesh:
            s, e = f(g_step, e)
        exact_acc += np.asarray(g_step).sum(0, keepdims=True)
        comp_acc += np.asarray(s)[:1]
    drift = np.abs(comp_acc - exact_acc).max() / np.abs(exact_acc).max()
    assert drift < 0.05, drift
    print("PASS grad_compression")


def check_ckpt_elastic():
    """Kill-and-resume loss parity across *different* plans: train K
    steps under plan A (dp=2, ZeRO extent 2), save, then restore under
    plan B (dp=4, extent 4) and continue — the stitched loss trace must
    match an uninterrupted plan-B run to 1e-5.  The manifest proves the
    saved and target extents differ, so the restore really resharded
    (elastic restart is a restore, not a migration)."""
    import shutil
    import tempfile
    from repro.configs import get_reduced
    from repro.core.plan import build_plan
    from repro.core.topology import ParallelConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_reduced("qwen3-1.7b")
    S, GB, N, K = 64, 8, 8, 4

    def trainer(dp, ckpt_dir, num_steps, ckpt_every):
        plan = build_plan(cfg, ParallelConfig(dp=dp),
                          devices=jax.devices()[:dp], impl="ref",
                          seq_len=S, global_batch=GB, zero="dp")
        tcfg = TrainerConfig(num_steps=num_steps, ckpt_dir=ckpt_dir,
                             ckpt_every=ckpt_every, log_every=1000)
        return Trainer(plan, plan.data_config(S, GB), tcfg)

    d = tempfile.mkdtemp(prefix="ckpt_elastic_")
    try:
        base = trainer(4, None, N, 10**6).run()
        assert len(base) == N

        t_a = trainer(2, d, K, K)          # saves step K on its way out
        assert t_a.plan.mem["zero_extent"] == 2
        part1 = t_a.run()
        t_a.ckpter.flush()

        t_b = trainer(4, d, N, 10**6)      # auto-restores at step K
        assert t_b.plan.mem["zero_extent"] == 4
        assert t_b.start_step == K, t_b.start_step
        m = t_b.ckpter.manifest()
        assert m["plan"]["dp"] == 2 and m["plan"]["zero_extent"] == 2
        assert max(e["shards"] for e in m["leaves"]) > 1   # truly sharded
        part2 = t_b.run()

        got = part1 + part2
        assert len(got) == N, (len(part1), len(part2))
        for i, (a, b) in enumerate(zip(got, base)):
            assert abs(a - b) < 1e-5, (i, a, b)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    print("PASS ckpt_elastic")


def check_offload_parity():
    """FPDT sequence-chunk pipeline (host KV offload) == resident
    double-ring: outputs and all three grads to 1e-5 on the ring 2x2,
    Ulysses hp=2 and combined hp×cp grids, zigzag on, on the Pallas
    kernel path (the jnp fallbacks are poisoned) — including packed
    documents whose boundaries straddle the chunk edges, the case the
    chunk-base BandMask shift exists for."""
    from repro.core.topology import ParallelConfig, make_mesh
    from repro.core.attention2d import (Attn2DConfig, attention_2d,
                                        chunked_attention_2d)
    from repro.core.zigzag import to_zigzag, from_zigzag
    from repro.kernels import ref as ref_mod
    from repro.runtime.offload import OffloadManager

    rng = np.random.default_rng(11)
    B, S, H, HKV, D, C = 1, 128, 4, 2, 16, 4
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    # packed stream whose document boundaries straddle the chunk edges
    # (S=128, C=4 -> edges at 32/64/96; docs start at 20/50/90)
    starts = [0, 20, 50, 90]
    doc_np = np.zeros((B, S), np.int32)
    for s0, s1 in zip(starts, starts[1:] + [S]):
        doc_np[:, s0:s1] = s0
    doc = jnp.asarray(doc_np)

    def boom(*a, **kw):
        raise AssertionError("jnp fallback selected on the chunked path")

    poisoned = ("attention_ref_chunked", "attention_bwd_ref_chunked")
    saved = {n: getattr(ref_mod, n) for n in poisoned}

    grids = [("ring2x2", 1, 2, 2), ("ulysses_hp2", 2, 1, 1),
             ("combined", 2, 2, 2)]
    for tag, hp, no, wi in grids:
        pc = ParallelConfig(dp=1, hp=hp, cp_outer=no, cp_inner=wi)
        mesh = make_mesh(pc)
        cp = pc.cp
        cfg = Attn2DConfig(hp=hp, n_out=no, w=wi, causal=True,
                           impl="pallas_interpret")
        for docs in (None, doc):
            def resident(q, k, v):
                qz, kz, vz = (to_zigzag(x, cp) for x in (q, k, v))
                dz = None if docs is None else to_zigzag(docs, cp)
                with mesh:
                    out = attention_2d(qz, kz, vz, mesh=mesh, cfg=cfg,
                                       doc_start=dz)
                out = from_zigzag(out, cp)
                return (out * w).sum(), out

            with mesh:
                (loss_r, o_r), g_r = jax.value_and_grad(
                    resident, argnums=(0, 1, 2), has_aux=True)(q, k, v)

            for n in poisoned:
                setattr(ref_mod, n, boom)
            try:
                mgr = OffloadManager()
                with mesh:
                    o_c, vjp = chunked_attention_2d(
                        q, k, v, mesh=mesh, cfg=cfg, chunks=C,
                        doc_start=docs, offload=mgr)
                    g_c = vjp(w)           # loss = (out*w).sum => d_out = w
            finally:
                for n, fn in saved.items():
                    setattr(ref_mod, n, fn)

            packed = "packed" if docs is not None else "dense"
            loss_c = float((np.asarray(o_c, np.float64)
                            * np.asarray(w, np.float64)).sum())
            rel = abs(loss_c - float(loss_r)) / max(1.0, abs(float(loss_r)))
            assert rel < 1e-5, (tag, packed, loss_c, float(loss_r))
            assert err(o_c, o_r) < 1e-5, (tag, packed, err(o_c, o_r))
            for a, b in zip(g_c, g_r):
                assert err(a, b) < 1e-5, (tag, packed, err(a, b))
            assert mgr.stalls == 0, (tag, packed, mgr.stats())
    print("PASS offload_parity")


CHECKS = {name[len("check_"):]: fn for name, fn in list(globals().items())
          if name.startswith("check_")}

if __name__ == "__main__":
    CHECKS[sys.argv[1]]()
