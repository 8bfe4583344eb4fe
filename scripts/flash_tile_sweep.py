"""Time the three flash kernels over a grid of tile sizes.

    python scripts/flash_tile_sweep.py [--seq 32768] [--heads 16]
        [--dim 128] [--batch 1] [--tiles 256,512,1024] [--reps 3]
        [--describe v5e:2x2]

For each ``(block_q, block_k)`` in ``--tiles`` squared, and 128x128 as
the baseline, it compiles ``flash_fwd``, ``flash_dq`` and ``flash_dkv``
(causal, bf16 operands in the folded ``(B*H, L, D)`` layout the ops pass
them in), times each on the attached chip (the median of ``--reps``
calls after one warm-up, each ended by ``block_until_ready``) and checks
its outputs against the 128x128 baseline.  It prints one table row per
pair and writes the rows as JSON to ``chiprun_out/flash_tile_sweep.json``.

``--describe <topology>`` compiles every pair for a described chip
instead (no chip needed, nothing runs): it shows which tiles the TPU
compiler accepts, and the VMEM limit each kernel asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import flash_attention as fa  # noqa: E402
from repro.kernels.ops import _make_params  # noqa: E402

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def kernel_fns(p):
    """name -> function of the folded operands that runs only that kernel."""
    band = fa._default_band(p)

    def fwd(q, k, v, out, do, lse):
        return fa._fwd(q, k, v, p, band=band)

    def dsum(out, do):
        return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                       axis=-1, keepdims=True)

    def dq(q, k, v, out, do, lse):
        return fa._bwd_dq(band, q, k, v, do, lse, dsum(out, do), p=p)

    def dkv(q, k, v, out, do, lse):
        return fa._bwd_dkv(band, q, k, v, do, lse, dsum(out, do), p=p)

    return dict(zip(KERNELS, (fwd, dq, dkv)))


def params(args, bq, bk):
    shape = (args.batch, args.seq, args.heads, args.dim)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    p, _, _ = _make_params(q, q, causal=True, window=None, softcap=0.0,
                           scale=None, kv_valid_len=None, block_q=bq,
                           block_k=bk, interpret=False)
    return p


def describe(args, pairs):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=args.describe)
    chip = SingleDeviceSharding(topo.devices[0])
    bh = args.batch * args.heads
    x = jax.ShapeDtypeStruct((bh, args.seq, args.dim), jnp.bfloat16,
                             sharding=chip)
    lse = jax.ShapeDtypeStruct((bh, args.seq, 1), jnp.float32,
                               sharding=chip)
    for bq, bk in pairs:
        p = params(args, bq, bk)
        row = {"block_q": bq, "block_k": bk}
        for name, fn in kernel_fns(p).items():
            n_tiles = fa._FWD_TILES if name == "flash_fwd" else fa._BWD_TILES
            row[f"{name}_vmem_mib"] = fa._vmem_bytes(p, args.dim,
                                                     n_tiles) / 2**20
            try:
                jax.jit(fn).lower(x, x, x, x, x, lse).compile()
                row[name] = "ok"
            except Exception as e:               # noqa: BLE001
                row[name] = f"refused: {str(e).splitlines()[0][:160]}"
        print(json.dumps(row), flush=True)


def timed(fn, operands, reps):
    out = fn(*operands)
    jax.block_until_ready(out)
    laps = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*operands)
        jax.block_until_ready(out)
        laps.append(time.perf_counter() - t0)
    return statistics.median(laps), out


def max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                     - y.astype(jnp.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def measure(args, pairs):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("flash_tile_sweep: no TPU (use --describe to compile only)",
              file=sys.stderr)
        sys.exit(2)
    bh = args.batch * args.heads
    ks = jax.random.split(jax.random.key(args.seed), 4)
    q, k, v, do = (jax.random.normal(kk, (bh, args.seq, args.dim),
                                     jnp.bfloat16) for kk in ks)
    p0 = params(args, 128, 128)
    out, lse = jax.jit(lambda q, k, v: fa._fwd(q, k, v, p0))(q, k, v)
    operands = (q, k, v, out, do, lse)
    base, rows = {}, []
    print(f"device {dev.device_kind}; B={args.batch} H={args.heads} "
          f"L={args.seq} D={args.dim} causal; seconds per call, median "
          f"of {args.reps}", flush=True)
    for bq, bk in pairs:
        p = params(args, bq, bk)
        row = {"block_q": bq, "block_k": bk,
               "grid_steps": bh * (args.seq // bq) * (args.seq // bk)}
        for name, fn in kernel_fns(p).items():
            jfn = jax.jit(fn)
            t0 = time.perf_counter()
            jfn.lower(*operands).compile()
            row[f"{name}_compile_s"] = time.perf_counter() - t0
            row[f"{name}_s"], res = timed(jfn, operands, args.reps)
            if (bq, bk) == (128, 128):
                base[name] = res
            row[f"{name}_max_diff"] = max_diff(res, base[name])
            del res
        row["bwd_s"] = row["flash_dq_s"] + row["flash_dkv_s"]
        row["step_flash_s"] = 2 * row["flash_fwd_s"] + row["bwd_s"]
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--tiles", default="256,512,1024")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--describe", default=None)
    args = ap.parse_args()
    tiles = [int(t) for t in args.tiles.split(",")]
    pairs = [(128, 128)] + [(bq, bk) for bq in tiles for bk in tiles]
    if args.describe:
        describe(args, pairs)
        return
    rows = measure(args, pairs)
    best = min(rows, key=lambda r: r["step_flash_s"])
    print("best (2 fwd + dq + dkv):", json.dumps(best))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "flash_tile_sweep.json"), "w") as f:
        json.dump({"args": vars(args), "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
