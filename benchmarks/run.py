"""Benchmark harness — one section per paper table.

Prints ``name,us_per_call,derived`` CSV rows.

* t2/t3/t4/t5 mirror the paper's Tables 2-5 through the §4.5 cost model
  re-based on TPU v5e (repro/analysis/cost.py); ``us_per_call`` is the
  modelled per-op/step time, ``derived`` the headline metric (MFU, bytes,
  speedup).  The model's collective volumes are cross-checked against
  compiled dry-run HLO in EXPERIMENTS.md §Roofline.
* ``micro_*`` rows are real wall-clock measurements on this host (1 CPU
  device): ref-path attention, interpret-mode kernel check, reduced-config
  train steps.
* ``tune`` (also standalone: ``run.py tune``) exercises the PlanTuner
  end to end — calibrated enumerate+score, top-3 measured live — and
  writes the predicted-vs-measured record to ``BENCH_tune.json``.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from repro.analysis.cost import (AttnCase, alltoall_time, attention_op_time,
                                 end_to_end_mfu, kv_chunk_bytes)

SEQS = [131072, 262144, 524288, 1048576]


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")


def t2_endtoend():
    """Table 2: LoongTrain grid vs DS-Ulysses (hp=sp) vs Megatron-CP
    (cp=sp) — 7B MHA & GQA on 32-way SP."""
    for h_kv, tag in ((32, "mha"), (8, "gqa")):
        for s in SEQS:
            rows = {}
            for hp in (1, 2, 4, 8, 16, 32):
                c = AttnCase(s=s, h_kv=h_kv, sp=32, hp=hp)
                rows[hp] = end_to_end_mfu(c)
            best_hp = max(rows, key=rows.get)
            _row(f"t2.{tag}.s{s}.ulysses", 0.0, f"mfu={rows[32]:.3f}")
            _row(f"t2.{tag}.s{s}.ringcp", 0.0, f"mfu={rows[1]:.3f}")
            _row(f"t2.{tag}.s{s}.loong_hp{best_hp}", 0.0,
                 f"mfu={rows[best_hp]:.3f};speedup_vs_ring="
                 f"{rows[best_hp]/max(rows[1],1e-9):.2f}x")


def t3_grid():
    """Table 3: hp×cp grid × placement × SC++ (64-way SP, 7B)."""
    for h_kv, tag in ((32, "mha"), (8, "gqa")):
        for s in (131072, 1048576):
            for hp in (1, 2, 4, 8, 16, 32):
                for placement in ("head_first", "context_first"):
                    for scpp in (True, False):
                        c = AttnCase(s=s, h_kv=h_kv, sp=64, hp=hp,
                                     placement=placement)
                        mfu = end_to_end_mfu(c, sc_pp=scpp)
                        _row(f"t3.{tag}.s{s}.hp{hp}cp{64//hp}."
                             f"{'hf' if placement=='head_first' else 'cf'}."
                             f"{'scpp' if scpp else 'base'}",
                             0.0, f"mfu={mfu:.3f}")


def t4_attention():
    """Table 4: single 2D-Attention op time + SeqAlltoAll volume."""
    for h_kv, tag in ((32, "mha"), (8, "gqa")):
        for s in (131072, 1048576):
            for hp in (1, 2, 4, 8, 16, 32):
                c = AttnCase(s=s, h_kv=h_kv, sp=64, hp=hp)
                t_op = attention_op_time(c) + attention_op_time(
                    c, backward=True)
                _row(f"t4.{tag}.s{s}.hp{hp}", t_op * 1e6,
                     f"a2a_bytes={alltoall_time(c)*50e9:.3e};"
                     f"kv_chunk={kv_chunk_bytes(c):.3e}")


def t5_double_ring():
    """Table 5: inner ring size sweep (cp=64 and cp=16)."""
    for cp, hp in ((64, 1), (16, 4)):
        for s in (131072, 1048576):
            base = None
            for w in (1, 2, 4, 8):
                c = AttnCase(s=s, h_kv=8, sp=64, hp=hp, w=w,
                             placement="context_first")
                t_op = attention_op_time(c)
                if base is None:
                    base = t_op
                _row(f"t5.gqa.s{s}.cp{cp}.w{w}", t_op * 1e6,
                     f"speedup_vs_w1={base/t_op:.2f}x")


def micro_ref_attention():
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    for (lq, h, d) in ((512, 8, 64), (1024, 8, 64)):
        q = jnp.asarray(rng.standard_normal((1, lq, h, d)), jnp.float32)
        f = jax.jit(lambda q: ops.flash_attention(q, q, q, causal=True,
                                                  impl="ref"))
        f(q).block_until_ready()
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            f(q).block_until_ready()
        us = (time.perf_counter() - t0) / n * 1e6
        _row(f"micro.ref_attn.s{lq}", us, f"host_flops={4*lq*lq*h*d:.2e}")


def micro_kernel_interpret():
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.float32)
    o_ref, _ = ref.attention_ref(q, q, q, causal=True)
    t0 = time.perf_counter()
    o_pal, _ = ops.flash_fwd_chunk(q, q, q, causal=True,
                                   impl="pallas_interpret",
                                   block_q=64, block_k=64)
    us = (time.perf_counter() - t0) * 1e6
    err = float(np.abs(np.asarray(o_pal) - np.asarray(o_ref)).max())
    _row("micro.pallas_interpret.s128", us, f"allclose_err={err:.2e}")


def micro_ring_step(out_path: str = "BENCH_ring.json"):
    """Micro wall-clock of one zigzag Double-Ring step (fwd + bwd) with a
    *traced* BandMask — flashref vs interpret-mode Pallas — written to
    ``BENCH_ring.json`` so the BENCH_* trajectory catches regressions on
    the ring hot path.  (Interpret mode emulates the kernel on CPU; its
    absolute time is interpreter overhead, not TPU time — the tracked
    signal is the trend of each impl against itself.)
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.ref import BandMask

    rng = np.random.default_rng(0)
    b, s_loc, hq, hkv, d = 1, 256, 8, 2, 64
    c, cp = s_loc // 2, 4
    i_rank, j_visit = 2, 1           # a generic off-diagonal ring step
    q = jnp.asarray(rng.standard_normal((b, s_loc, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s_loc, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s_loc, hkv, d)), jnp.float32)

    bench = {"config": {"b": b, "s_loc": s_loc, "hq": hq, "hkv": hkv,
                        "d": d, "cp": cp, "step": [i_rank, j_visit],
                        "block": 64},
             "cases": []}
    for impl in ("flashref", "pallas_interpret"):
        fwd = jax.jit(lambda i, j: ops.flash_fwd_chunk(
            q, k, v, causal=True, band=BandMask.zigzag(i, j, c, cp),
            impl=impl, block_q=64, block_k=64))
        out, lse = fwd(jnp.int32(i_rank), jnp.int32(j_visit))
        jax.block_until_ready((out, lse))
        do = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
        bwd = jax.jit(lambda i, j: ops.flash_bwd_chunk(
            q, k, v, out, lse, do, causal=True,
            band=BandMask.zigzag(i, j, c, cp),
            impl=impl, block_q=64, block_k=64))
        jax.block_until_ready(bwd(jnp.int32(i_rank), jnp.int32(j_visit)))
        n = 5
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fwd(jnp.int32(i_rank), jnp.int32(j_visit)))
        fwd_us = (time.perf_counter() - t0) / n * 1e6
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(bwd(jnp.int32(i_rank), jnp.int32(j_visit)))
        bwd_us = (time.perf_counter() - t0) / n * 1e6
        bench["cases"].append({"impl": impl, "fwd_us": round(fwd_us, 1),
                               "bwd_us": round(bwd_us, 1)})
        _row(f"micro.ring_step.{impl}.fwd", fwd_us, f"s_loc={s_loc}")
        _row(f"micro.ring_step.{impl}.bwd", bwd_us, f"s_loc={s_loc}")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2)


def micro_train_step():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.core.plan import build_plan
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import forward_loss, init_params

    for arch in ("qwen3-1.7b", "falcon-mamba-7b", "qwen3-moe-30b-a3b"):
        cfg = get_reduced(arch)
        plan = build_plan(cfg, devices=jax.devices()[:1], impl="ref",
                          seq_len=64, global_batch=4)
        rt = plan.rt
        params = init_params(cfg, jax.random.PRNGKey(0))
        data = SyntheticLM(plan.data_config(64, 4, zigzag=False), cfg)
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
        with plan.mesh:
            g = jax.jit(jax.grad(
                lambda p: forward_loss(p, batch, rt, cfg)[0]))
            jax.block_until_ready(g(params))
            t0 = time.perf_counter()
            n = 3
            for _ in range(n):
                jax.block_until_ready(g(params))
        us = (time.perf_counter() - t0) / n * 1e6
        _row(f"micro.train_step.{arch}", us, "reduced-config grad step")


def bench_train_step(out_path: str = "BENCH_train_step.json"):
    """Gradient-accumulation sweep + sync-free-trainer-loop measurement,
    written to ``BENCH_train_step.json``.

    For ``grad_accum`` ∈ {1, 2, 4} at a fixed global batch, times the
    full jitted train step (fwd+bwd+AdamW) and derives steps/s.  For
    each, the driving loop is timed two ways: ``sync`` calls
    ``float(metrics["loss"])`` every step (the seed trainer's per-step
    device sync) and ``async`` only materializes at the end (the current
    trainer's ``log_every`` behaviour) — the gap is the dispatch
    pipelining recovered by keeping metrics on device.
    """
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.core.plan import build_plan
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import init_params
    from repro.train.optimizer import init_opt_state
    from repro.train.train_step import jit_train_step

    cfg = get_reduced("qwen3-1.7b")
    gb, seq, n = 8, 64, 8
    bench = {"config": {"arch": cfg.name, "global_batch": gb,
                        "seq_len": seq, "steps": n}, "cases": []}
    for accum in (1, 2, 4):
        plan = build_plan(cfg, devices=jax.devices()[:1], impl="ref",
                          grad_accum=accum, seq_len=seq, global_batch=gb)
        data = SyntheticLM(plan.data_config(seq, gb), cfg)
        params = init_params(cfg, jax.random.PRNGKey(0))
        with plan.mesh:
            step, p_sh, o_sh = jit_train_step(plan, params, donate=False)
            opt = init_opt_state(params)
            batches = [{k: jnp.asarray(v) for k, v in data.batch(i).items()}
                       for i in range(n)]
            jax.block_until_ready(step(params, opt, batches[0]))

            def loop(sync: bool):
                p, o = params, opt
                t0 = time.perf_counter()
                for i in range(n):
                    p, o, m = step(p, o, batches[i])
                    if sync:
                        float(m["loss"])
                jax.block_until_ready((p, o))
                return n / (time.perf_counter() - t0)

            sps_sync, sps_async = loop(True), loop(False)
        bench["cases"].append({"grad_accum": accum,
                               "steps_per_s_sync": round(sps_sync, 3),
                               "steps_per_s_async": round(sps_async, 3)})
        _row(f"micro.accum{accum}.sync", 1e6 / sps_sync,
             f"steps_per_s={sps_sync:.2f}")
        _row(f"micro.accum{accum}.async", 1e6 / sps_async,
             f"steps_per_s={sps_async:.2f};"
             f"speedup={sps_async / sps_sync:.2f}x")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2)


def bench_serve(out_path: str = "BENCH_serve.json"):
    """Continuous-batching paged engine vs the fixed-batch contiguous
    baseline on uniform and mixed-length request streams, written to
    ``BENCH_serve.json``.

    Both schedulers run the same reduced model on this host with the same
    4 decode slots, their jitted steps compiled once (rep 0 of each
    stream warms, rep 1 is timed), and the **same KV-cache byte budget**
    (2048 token-slots): the fixed baseline spends it as 4 contiguous
    worst-case caches of 512, the paged engine as a shared 128-block
    pool.  The fixed baseline processes requests in submission-order
    groups: prompts padded to the per-stream max, decode runs until the
    *longest* request of the group finishes — the straggler effect the
    engine's in-place retirement removes.  Tokens/s counts only requested
    tokens; per-request latency is submit→finish (queueing included).
    """
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.core.plan import build_plan
    from repro.launch.serve import generate, make_generate_fns
    from repro.models.model import init_params
    from repro.serve import SamplingParams, ServeEngine

    cfg = get_reduced("qwen3-1.7b")
    plan = build_plan(cfg, devices=jax.devices()[:1], impl="ref")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = plan.rt
    b_slots = 4
    rng = np.random.default_rng(0)
    # (prompt_len, gen) per request; mixed spans ~32..512 total tokens
    # with bimodal gen lengths — the straggler case fixed batching pays for
    streams = {
        "uniform": [(64, 32)] * 8,
        "mixed": [(int(p), int(g)) for p, g in
                  zip(rng.integers(24, 385, size=8),
                      rng.choice([8, 16, 96, 128], size=8))],
    }
    max_total = max(p + g for reqs in streams.values() for p, g in reqs)
    prompts = {name: [rng.integers(0, cfg.vocab, size=p)
                      for p, _ in reqs]
               for name, reqs in streams.items()}

    bench = {"config": {"arch": cfg.name, "max_batch": b_slots,
                        "page_size": 16, "streams": streams},
             "cases": []}

    def pctl(lats, q):
        lats = sorted(lats)
        return lats[min(len(lats) - 1, int(len(lats) * q))]

    # -- paged engine: one jit set reused across streams.  Same pool bytes
    # (and slots) as the baseline's 4 × 512 contiguous caches, spent as a
    # shared 128-block pool — decode views follow the active lengths.
    from repro.serve.engine import EngineConfig
    assert max_total <= 512, max_total
    spec = EngineConfig(page_size=16, num_blocks=128,
                        max_blocks_per_seq=32, max_batch=b_slots,
                        prefill_chunk=128)
    with plan.mesh:
        eng = ServeEngine(plan, params, spec)
        eng.warmup(prompt_lens=(16, 32, 64, 128))   # compile all buckets
        for name, reqs in streams.items():
            for rep in range(2):       # rep 0 warms every (chunk, view) jit
                for (p_len, gen), p in zip(reqs, prompts[name]):
                    eng.submit(p, SamplingParams(), max_new_tokens=gen)
                res = eng.run()
                lats = [r["latency_s"] for r in res["requests"].values()]
            bench["cases"].append({
                "name": f"{name}.paged",
                "tokens_per_s": round(res["tokens_per_s"], 2),
                "p50_ms": round(pctl(lats, 0.5) * 1e3, 1),
                "p99_ms": round(pctl(lats, 0.99) * 1e3, 1),
                "generated": res["generated"],
                "wall_s": round(res["wall_s"], 3)})
            _row(f"serve.{name}.paged", res["wall_s"] * 1e6,
                 f"tok_s={res['tokens_per_s']:.1f}")

    # -- fixed-batch baseline: launch.serve.generate itself (token parity
    # with the engine pinned by tests/test_serve.py), with its jitted
    # steps hoisted once via make_generate_fns so repeated groups reuse
    # compiles.  Prompts pad to the *per-stream* max and each group
    # decodes to its own longest request — the baseline's honest best
    # schedule at fixed batching.
    fns = make_generate_fns(cfg, rt)

    def run_fixed(reqs, toks):
        s_pad = max(p for p, _ in reqs)
        t0 = time.perf_counter()
        lats, generated = [], 0
        for i in range(0, len(reqs), b_slots):
            group = reqs[i:i + b_slots]
            rows = toks[i:i + b_slots]
            tokens = np.zeros((b_slots, s_pad), np.int32)
            for j, r in enumerate(rows):
                tokens[j, :len(r)] = r
            out = generate(params, cfg, rt, jnp.asarray(tokens),
                           gen=max(g for _, g in group), fns=fns)
            jax.block_until_ready(out)
            t_group = time.perf_counter() - t0
            generated += sum(g for _, g in group)
            lats += [t_group] * len(group)     # group finishes together
        return generated, time.perf_counter() - t0, lats

    with plan.mesh:
        for name, reqs in streams.items():
            run_fixed(reqs, prompts[name])         # warm the jitted steps
            generated, wall, lats = run_fixed(reqs, prompts[name])
            tok_s = generated / max(wall, 1e-9)
            bench["cases"].append({
                "name": f"{name}.fixed",
                "tokens_per_s": round(tok_s, 2),
                "p50_ms": round(pctl(lats, 0.5) * 1e3, 1),
                "p99_ms": round(pctl(lats, 0.99) * 1e3, 1),
                "generated": generated,
                "wall_s": round(wall, 3)})
            _row(f"serve.{name}.fixed", wall * 1e6,
                 f"tok_s={tok_s:.1f}")

    by_name = {c["name"]: c for c in bench["cases"]}
    for name in streams:
        speed = (by_name[f"{name}.paged"]["tokens_per_s"]
                 / max(by_name[f"{name}.fixed"]["tokens_per_s"], 1e-9))
        bench["config"][f"{name}_paged_speedup"] = round(speed, 2)
        _row(f"serve.{name}.speedup", 0.0, f"paged_vs_fixed={speed:.2f}x")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2)


def bench_packed(out_path: str = "BENCH_packed.json"):
    """Masked-block skipping vs dense-masked packing on a mixed-length
    document stream, written to ``BENCH_packed.json``.

    A ``PackedLM`` stream (the real pipeline, mixed 64–320-token docs in
    a 1024 window) drives the doc-masked flash kernel twice: ``skip``
    (cross-document K blocks skipped via the doc-start predicate — the
    default) and ``dense`` (identical element-wise mask, skip disabled).
    Numerics are bitwise identical (pinned by tests); the tracked signal
    is the wall-clock of each mode plus the *deterministic* fraction of
    grid blocks each mode executes — the long-tail win of packing,
    measured rather than assumed.  (Interpret mode: absolute times are
    interpreter overhead; the trend of each mode against itself and the
    block fractions are the signal.)
    """
    import jax
    import jax.numpy as jnp
    from repro.data.pipeline import DataConfig, PackedLM
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    # MXU-sized blocks: the per-block matmul body dominates the
    # interpreter's fixed per-grid-step cost, so skipped blocks show up
    # in wall time, not just the block count.
    b, s, hq, hkv, d, blk = 1, 1024, 4, 2, 128, 128
    data = PackedLM(DataConfig(vocab=211, seq_len=s, global_batch=b,
                               cp=1, zigzag=False,
                               doc_len_range=(64, 320)))
    doc_np = np.asarray(data.batch(0)["doc_start"])
    doc = jnp.asarray(doc_np)
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)

    def exec_blocks(skip: bool):
        """Fraction of (q-block, k-block) grid steps the forward kernel
        runs (uniform causal band; doc table nondecreasing)."""
        runs = total = 0
        for q0 in range(0, s, blk):
            for k0 in range(0, s, blk):
                total += 1
                if k0 > q0 + blk - 1:               # causal block skip
                    continue
                if skip and k0 + blk - 1 < doc_np[0, q0]:
                    continue                        # cross-document skip
                runs += 1
        return runs / total

    n_docs = sum(len(ds) for ds in data.boundaries(0))
    bench = {"config": {"b": b, "s": s, "hq": hq, "hkv": hkv, "d": d,
                        "block": blk, "doc_len_range": [64, 320],
                        "n_docs": n_docs},
             "cases": []}
    # jit both modes up front, then interleave timed reps (skip, dense,
    # skip, ...) and take per-mode medians: host-load drift hits both
    # modes alike instead of whichever ran second.
    fns, times = {}, {}
    do = None
    for mode, skip in (("skip", True), ("dense", False)):
        kw = dict(causal=True, q_doc_start=doc, doc_skip=skip,
                  impl="pallas_interpret", block_q=blk, block_k=blk)
        fwd = jax.jit(lambda q, k, v, kw=kw: ops.flash_fwd_chunk(
            q, k, v, **kw))
        out, lse = fwd(q, k, v)
        jax.block_until_ready((out, lse))
        if do is None:
            do = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
        bwd = jax.jit(lambda q, k, v, out, lse, do, kw=kw:
                      ops.flash_bwd_chunk(q, k, v, out, lse, do, **kw))
        jax.block_until_ready(bwd(q, k, v, out, lse, do))
        fns[mode] = (fwd, bwd, out, lse)
        times[mode] = {"fwd": [], "bwd": []}
    for _ in range(5):
        for mode in ("skip", "dense"):
            fwd, bwd, out, lse = fns[mode]
            for tag, run in (("fwd", lambda: fwd(q, k, v)),
                             ("bwd", lambda: bwd(q, k, v, out, lse, do))):
                w0, c0 = time.perf_counter(), time.process_time()
                jax.block_until_ready(run())
                times[mode].setdefault(tag, []).append(
                    (time.perf_counter() - w0, time.process_time() - c0))
    for mode, skip in (("skip", True), ("dense", False)):
        case = {"mode": mode, "blocks_frac": round(exec_blocks(skip), 4)}
        for tag in ("fwd", "bwd"):
            wall, cpu = zip(*times[mode][tag])
            # cpu (process) time is the gated metric: on a loaded host it
            # tracks work done, where wall time tracks the scheduler
            case[f"{tag}_us"] = round(float(np.median(wall)) * 1e6, 1)
            case[f"{tag}_cpu_us"] = round(float(np.median(cpu)) * 1e6, 1)
        bench["cases"].append(case)
        _row(f"packed.{mode}.fwd", case["fwd_us"],
             f"cpu_us={case['fwd_cpu_us']};"
             f"blocks_frac={case['blocks_frac']}")
        _row(f"packed.{mode}.bwd", case["bwd_us"],
             f"cpu_us={case['bwd_cpu_us']};"
             f"blocks_frac={case['blocks_frac']}")
    by = {c["mode"]: c for c in bench["cases"]}
    for m in ("fwd_cpu_us", "bwd_cpu_us"):
        bench["config"][f"skip_speedup_{m[:3]}"] = round(
            by["dense"][m] / max(by["skip"][m], 1e-9), 2)
    bench["config"]["blocks_saved"] = round(
        1.0 - by["skip"]["blocks_frac"] / by["dense"]["blocks_frac"], 4)
    _row("packed.skip.speedup", 0.0,
         f"fwd={bench['config']['skip_speedup_fwd']}x;"
         f"bwd={bench['config']['skip_speedup_bwd']}x;"
         f"blocks_saved={bench['config']['blocks_saved']} (cpu-time)")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2)


def bench_tune(out_path: str = "BENCH_tune.json"):
    """PlanTuner predicted-vs-measured: enumerate+score the reduced
    config's plan space for this host's devices with *calibrated* cost
    constants, measure the analytic top-3 live (jit + timed steps), and
    write both numbers per candidate to ``BENCH_tune.json``.

    The tracked signal is the measured step time of the tuner's picks
    (does the winner stay fast?) — the prediction is recorded alongside
    as the model-quality trajectory (``ratio`` = measured/predicted; on
    this CPU host expect O(1–50): the analytic model is a TPU network
    model, calibration only rescales its peaks to host ballpark).
    """
    from repro.configs import get_reduced
    from repro.tune import tune
    from repro.tune.calibrate import constants_from_raw, run_microbenchmarks

    cfg = get_reduced("qwen3-1.7b")
    import jax
    const = constants_from_raw(run_microbenchmarks())   # hermetic: no file
    seq, gb = 256, 8
    result = tune(cfg, num_devices=len(jax.devices()), seq_len=seq,
                  global_batch=gb, memory_budget_gb=1.0, const=const,
                  measure_top_k=3, arch=cfg.name)
    bench = {"config": {"arch": cfg.name, "seq_len": seq,
                        "global_batch": gb,
                        "devices": len(jax.devices()),
                        "space_size": result.space_size,
                        "calibration": const.source},
             "cases": []}
    for s in result.ranked[:3]:
        case = {"tag": s.tag, "predicted_ms": round(s.score_s * 1e3, 3)}
        if s.measured_s is not None:
            case["measured_ms"] = round(s.measured_s * 1e3, 3)
            case["ratio"] = round(s.measured_s / max(s.score_s, 1e-12), 2)
        bench["cases"].append(case)
        _row(f"tune.{s.tag}", (s.measured_s or s.score_s) * 1e6,
             f"predicted_ms={case['predicted_ms']}")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2)


def _ckpt_worker():
    """Subprocess body for ``bench_ckpt`` (needs 8 fake devices, so it
    cannot run in the caller's process — the device count locks at first
    jax use).  Prints one JSON object on the last stdout line."""
    import shutil
    import tempfile

    import jax
    from repro.configs import get_reduced
    from repro.core.plan import build_plan
    from repro.core.topology import ParallelConfig
    from repro.models.model import init_params
    from repro.runtime.checkpoint import CheckpointManager
    from repro.train.optimizer import init_opt_state

    cfg = get_reduced("qwen3-1.7b")
    grids = [("replica.x1", ParallelConfig(dp=2), "replica"),
             ("zero_dp.x2", ParallelConfig(dp=2), "dp"),
             ("zero_dp_sp.x8",
              ParallelConfig(dp=2, hp=2, cp_outer=1, cp_inner=2), "dp_sp")]
    cases = []
    for tag, pc, zero in grids:
        plan = build_plan(cfg, pc, devices=jax.devices()[:pc.num_devices],
                          impl="ref", seq_len=64, global_batch=8,
                          zero=zero)
        with plan.mesh:
            params = init_params(cfg, jax.random.PRNGKey(0))
            p_sh = plan.param_shardings(params)
            params = jax.device_put(params, p_sh)
            opt = jax.device_put(init_opt_state(params),
                                 plan.opt_shardings(p_sh))
        state = {"params": params, "opt": opt}
        d = tempfile.mkdtemp(prefix=f"bench_ckpt_{zero}_")
        try:
            mgr = CheckpointManager(d, plan=plan, keep=2)
            stalls, writes, saves, resumes = [], [], [], []
            for rep in range(3):
                t0 = time.perf_counter()
                mgr.save_async(state, 2 * rep + 1)
                stalls.append(time.perf_counter() - t0)  # snapshot only
                t0 = time.perf_counter()
                mgr.flush()
                writes.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                mgr.save(state, 2 * rep + 2)
                saves.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                _, step = mgr.restore(state)
                resumes.append(time.perf_counter() - t0)
                assert step == 2 * rep + 2
            man = mgr.manifest()
            cases.append({
                "tag": tag, "zero_extent": plan.mem["zero_extent"],
                "bytes_per_host": man["bytes_per_host"],
                "max_shards": max(e["shards"] for e in man["leaves"]),
                "stall_ms": round(float(np.median(stalls)) * 1e3, 2),
                "write_ms": round(float(np.median(writes)) * 1e3, 2),
                "save_ms": round(float(np.median(saves)) * 1e3, 2),
                "resume_ms": round(float(np.median(resumes)) * 1e3, 2),
                "model_bytes_per_host": int(plan.mem["ckpt_bytes_host"]),
            })
        finally:
            shutil.rmtree(d, ignore_errors=True)
    print(json.dumps({"cases": cases}))


def bench_ckpt(out_path: str = "BENCH_ckpt.json"):
    """Plan-aware sharded checkpointing across ZeRO extents, written to
    ``BENCH_ckpt.json``.

    One worker subprocess (8 fake devices) saves+restores the same
    reduced train state under extents 1 (replica), 2 (ZeRO over dp=2)
    and 8 (dp·sp) and reports, per extent: the ``save_async`` **stall**
    (the device→host snapshot — the only part that blocks the step
    loop), the background write time, the blocking-save and
    time-to-resume wall times, and the manifest's ``bytes_per_host``.
    The layout claim under test: per-host checkpoint bytes shrink with
    the ZeRO extent (each host serializes only its shards), so the
    recorded ``bytes_shrink_with_extent`` must stay true.
    """
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # 8 virtual CPU devices; pinned to the CPU so the child never competes
    # with this process for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    res = subprocess.run([sys.executable, os.path.abspath(__file__),
                          "_ckpt_worker"], capture_output=True, text=True,
                         timeout=900, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    data = json.loads(res.stdout.strip().splitlines()[-1])
    bench = {"config": {"arch": "qwen3-1.7b", "seq_len": 64,
                        "global_batch": 8, "devices": 8,
                        "state": "params + opt (m, v, step)"},
             "cases": data["cases"]}
    by_extent = sorted(data["cases"], key=lambda c: c["zero_extent"])
    bench["config"]["bytes_shrink_with_extent"] = all(
        a["bytes_per_host"] > b["bytes_per_host"]
        for a, b in zip(by_extent, by_extent[1:]))
    for c in data["cases"]:
        _row(f"ckpt.{c['tag']}.stall", c["stall_ms"] * 1e3,
             f"bytes_per_host={c['bytes_per_host']};"
             f"extent={c['zero_extent']};shards={c['max_shards']}")
        _row(f"ckpt.{c['tag']}.resume", c["resume_ms"] * 1e3,
             f"save_ms={c['save_ms']};write_ms={c['write_ms']}")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2)


def _offload_worker():
    """Subprocess body for ``bench_offload`` (needs 8 fake devices for the
    combined hp×cp grid).  Prints one JSON object on the last stdout line."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.core.attention2d import (Attn2DConfig, attention_2d,
                                        chunked_attention_2d)
    from repro.core.plan import plan_memory
    from repro.core.topology import ParallelConfig, make_mesh
    from repro.core.zigzag import from_zigzag, to_zigzag
    from repro.runtime.offload import OffloadManager

    cases = []

    # -- memory model: longest trainable sequence at a fixed HBM budget.
    # Deterministic (no wall clock anywhere): the chunk pipeline keeps
    # only the active+prefetched fraction 2/C of the sequence-extensive
    # bytes resident, so depth C buys exactly C/2× sequence once C >= 2.
    cfg = get_reduced("qwen3-1.7b")
    pc = ParallelConfig(dp=1, hp=2, cp_outer=2, cp_inner=2)
    budget_gb = 0.05
    base = None
    for chunks in (1, 4, 8, 16):
        _, _, _, mem = plan_memory(cfg, pc, remat="none",
                                   memory_budget_gb=budget_gb,
                                   seq_len=131072, global_batch=8,
                                   offload_chunks=chunks)
        ms = mem["max_seq_at_budget"]
        if base is None:
            base = ms
        cases.append({
            "kind": "max_seq", "tag": f"max_seq.off{chunks}",
            "chunks": chunks, "max_seq_at_budget": int(ms),
            "seq_ratio": round(ms / max(base, 1), 2),
            "act_dev_bytes": int(mem["act_dev"]),
            "act_host_bytes": int(mem["act_host"]),
            "wire_ms": round(mem["offload_wire_s"] * 1e3, 3)})

    # -- measured: chunked pipeline vs resident double-ring, same grid
    acfg = Attn2DConfig(hp=pc.hp, n_out=pc.cp_outer, w=pc.cp_inner,
                        causal=True, impl="ref")
    mesh = make_mesh(pc)
    cp = pc.cp
    rng = np.random.default_rng(0)
    B, S, H, HKV, D = 1, 512, 4, 2, 16
    chunks = 4
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    qkv_bytes = sum(int(np.asarray(x).nbytes) for x in (q, k, v))

    def resident_loss(q, k, v):
        qz, kz, vz = (to_zigzag(x, cp) for x in (q, k, v))
        out = attention_2d(qz, kz, vz, mesh=mesh, cfg=acfg)
        return (from_zigzag(out, cp) * do).sum()

    res_grad = jax.jit(jax.value_and_grad(resident_loss, argnums=(0, 1, 2)))

    def run_resident():
        with mesh:
            return jax.block_until_ready(res_grad(q, k, v))

    def run_chunked(mgr):
        with mesh:
            out, vjp = chunked_attention_2d(q, k, v, mesh=mesh, cfg=acfg,
                                            chunks=chunks, offload=mgr)
            return jax.block_until_ready((out, vjp(do)))

    run_resident()                       # compile warm-up
    run_chunked(OffloadManager())
    times = {"resident": [], "chunked": []}
    stats = None
    for _ in range(5):
        t0, c0 = time.perf_counter(), time.process_time()
        run_resident()
        times["resident"].append((time.perf_counter() - t0,
                                  time.process_time() - c0))
        mgr = OffloadManager()
        t0, c0 = time.perf_counter(), time.process_time()
        run_chunked(mgr)
        times["chunked"].append((time.perf_counter() - t0,
                                 time.process_time() - c0))
        stats = mgr.stats()

    med = {}
    for mode in ("resident", "chunked"):
        wall, cpu = zip(*times[mode])
        med[mode] = {"wall_us": round(float(np.median(wall)) * 1e6, 1),
                     "cpu_us": round(float(np.median(cpu)) * 1e6, 1)}
    cases.append(dict(kind="step", tag="step.resident", mode="resident",
                      **med["resident"]))
    cases.append(dict(
        kind="step", tag=f"step.chunked.off{chunks}", mode="chunked",
        chunks=chunks, **med["chunked"],
        overhead=round(med["chunked"]["wall_us"]
                       / max(med["resident"]["wall_us"], 1e-9), 2),
        stalls=int(stats["stalls"]),
        peak_device_bytes=int(stats["peak_device_bytes"]),
        peak_device_frac=round(stats["peak_device_bytes"]
                               / max(qkv_bytes, 1), 3),
        h2d_bytes=int(stats["h2d_bytes"]),
        d2h_bytes=int(stats["d2h_bytes"])))
    print(json.dumps({"cases": cases}))


def bench_offload(out_path: str = "BENCH_offload.json"):
    """FPDT sequence-chunk pipelining with host KV offload, written to
    ``BENCH_offload.json``.

    One worker subprocess (8 fake devices) records the two sides of the
    offload trade:

    * **max trainable sequence** at a fixed HBM budget, straight from the
      plan memory model at depths 1/4/8/16 — deterministic, so the gate
      allows no noise; the resident fraction is ``2/C`` (active + next
      chunk), so depth 8 must buy ≥ 4× sequence over the resident
      baseline (``seq_gain_4x_at_off8``).
    * **step overhead**: measured fwd+bwd wall time of the chunked
      pipeline (depth 4) against the resident double-ring on the same
      combined hp=2 × cp=2x2 grid, with the ``OffloadManager``
      telemetry — ``stalls`` must stay 0 (every chunk's H2D copy lands
      before the pipeline reads it) and ``peak_device_frac`` records the
      HBM residency actually held.
    """
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # 8 virtual CPU devices; pinned to the CPU so the child never competes
    # with this process for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    res = subprocess.run([sys.executable, os.path.abspath(__file__),
                          "_offload_worker"], capture_output=True,
                         text=True, timeout=900, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    data = json.loads(res.stdout.strip().splitlines()[-1])
    by = {c["tag"]: c for c in data["cases"]}
    bench = {"config": {"arch": "qwen3-1.7b", "budget_gb": 0.05,
                        "plan_seq_len": 131072, "devices": 8,
                        "grid": "dp1.hp2.cp2x2",
                        "seq_gain_4x_at_off8":
                            by["max_seq.off8"]["seq_ratio"] >= 4.0,
                        "pipeline_stalls":
                            by["step.chunked.off4"]["stalls"]},
             "cases": data["cases"]}
    for c in data["cases"]:
        if c["kind"] == "max_seq":
            _row(f"offload.{c['tag']}", 0.0,
                 f"max_seq={c['max_seq_at_budget']};"
                 f"ratio={c['seq_ratio']}x;wire_ms={c['wire_ms']}")
        elif c["mode"] == "resident":
            _row("offload.step.resident", c["wall_us"],
                 f"cpu_us={c['cpu_us']}")
        else:
            _row(f"offload.{c['tag']}", c["wall_us"],
                 f"overhead={c['overhead']}x;stalls={c['stalls']};"
                 f"peak_dev_frac={c['peak_device_frac']}")
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=2)


def main() -> None:
    sections = {"ring": micro_ring_step, "train": bench_train_step,
                "serve": bench_serve, "tune": bench_tune,
                "packed": bench_packed, "ckpt": bench_ckpt,
                "offload": bench_offload}
    if len(sys.argv) > 1 and sys.argv[1] == "_ckpt_worker":
        _ckpt_worker()
        return
    if len(sys.argv) > 1 and sys.argv[1] == "_offload_worker":
        _offload_worker()
        return
    if len(sys.argv) > 1 and sys.argv[1] in sections:
        print("name,us_per_call,derived")
        sections[sys.argv[1]]()
        return
    print("name,us_per_call,derived")
    t2_endtoend()
    t3_grid()
    t4_attention()
    t5_double_ring()
    micro_ref_attention()
    micro_kernel_interpret()
    micro_ring_step()
    micro_train_step()
    bench_train_step()
    bench_serve()
    bench_tune()
    bench_packed()
    bench_ckpt()
    bench_offload()


if __name__ == "__main__":
    main()
