"""One run of one cell: set up, check the first steps, measure, compare.

1. Set-up: the program's trainer for the cell (``bench/program.py``), its
   step compiled, the benchmark's weights in place.
2. The first ``check.steps`` steps, through ``Trainer.run()`` and the
   trainer's own data source: their losses, the first gradient (read from
   the optimizer's first moment) and the weights' change are kept for the
   comparison, and the batches the source gave are checked against the
   benchmark's stream after the window.  They also warm
   the step, and their time sets how many steps fill ``--seconds``.
3. The window: that many further steps through the same ``Trainer.run()``
   and compiled step, timed on the host clock to the last step's end.
   With ``--trace 1`` the window runs under the profiler.
4. The program's state is dropped, then the reference trains the same
   steps from the same seed, and ``bench/check.py`` compares.
5. One JSON line on stdout; the compared numbers, each with its limit, as
   the last lines on stderr.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import time

import jax
import numpy as np
from jax.sharding import Mesh

from bench import check, flops, reference, trace
from bench.manifest import Manifest
from bench.peaks import Peak, peak_for
from bench.program import Program
from bench.traffic import Stream
from bench.weights import Dims, seed_key

TRACE_DIR = ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: dict
    config: dict
    traffic: dict
    dims: Dims
    chips: int
    steps: int                 # steps in the traced window
    work: flops.StepWork       # one step's required work
    peak: Peak
    trace: trace.Summary


def _note(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def chips_for(cell: dict, require_tpu: bool = True) -> list:
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < cell["chips"]:
        raise NoChip(f"needs {cell['chips']} chips, JAX found "
                     f"{len(devices)}")
    return devices[:cell["chips"]]


def enable_cache():
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), holding every program however short its
    compile, so that a second run compiles nothing."""
    from repro.runtime.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return enable_compile_cache()


def checked_steps(prog: Program, k: int) -> tuple[dict, float]:
    """Steps 1..k of the program: its readings, and the time of a step."""
    loss = prog.run(0, 1)
    grad = prog.first_grad()
    t0 = time.perf_counter()
    loss += prog.run(1, k)
    prog.sync()
    step_s = (time.perf_counter() - t0) / max(k - 1, 1)
    return {"loss": loss, "grad": grad, "change": prog.change()}, step_s


def reference_readings(dims: Dims, traffic: dict, seed: int, k: int,
                       devices, prec: str = "fp32",
                       half: bool = False) -> dict:
    """The reference's readings for steps 1..k of the cell's seed, split
    over the cell's devices.  ``half`` leaves the second half of every
    row's tokens out of the loss (a fault, for setting limits)."""
    stream = Stream(traffic, dims.vocab, seed)
    batches = []
    for i in range(k):
        tokens, labels = stream.logical(i)
        if half:
            labels = labels.copy()
            labels[:, labels.shape[1] // 2:] = -1
        batches.append((tokens, labels))
    mesh = Mesh(np.array(devices), ("t",)) if len(devices) > 1 else None
    return reference.train(dims, reference.Opt(**traffic["optimizer"]),
                           seed_key(seed), batches, prec, mesh)


def run(man: Manifest, name: str, seed: int, seconds: float, traced: bool,
        t_start: float, *, require_tpu: bool = True,
        peak: Peak | None = None) -> tuple[dict, list]:
    """Returns (result line, check lines).  ``require_tpu`` and ``peak``
    are for tests, which drive a run on the CPU with a stated peak."""
    cell = man.cell(name)
    config, traffic, ck = man.config(cell), man.traffic(cell), man.check(cell)
    dims = Dims.from_config(config)
    devices = chips_for(cell, require_tpu)
    enable_cache()
    kind = devices[0].device_kind
    peak = peak or peak_for(kind)

    prog = Program(config, traffic, dims, devices, seed)
    if require_tpu and not prog.kernels:
        raise RuntimeError("the compiled step holds no tpu_custom_call: the "
                           "Pallas kernels are not on the timed path")
    k = int(ck["steps"])
    got, step_s = checked_steps(prog, k)
    n = max(1, round(seconds / step_s))
    setup_s = time.perf_counter() - t_start
    _note(f"set-up {setup_s:.2f}s, checked steps' losses {got['loss']}, "
          f"step {step_s:.4f}s, window of {n} steps, kernels "
          f"{prog.kernels}")

    if traced:
        logdir = os.path.join(man.root, TRACE_DIR)
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(logdir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        window_loss = prog.run(k, k + n)
        prog.sync()
    window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    failed = sum(not math.isfinite(x) for x in window_loss)
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    hbm = prog.hbm_bytes()
    kernels = prog.kernels
    prog.close()
    got["data"] = prog.data_gaps(k)
    del prog
    gc.collect()

    _note(f"window {window_s:.4f}s, hbm {hbm} B, peak in use {mem_peak} B")
    t0 = time.perf_counter()
    ref = reference_readings(dims, traffic, seed, k, devices)
    _note(f"reference {time.perf_counter() - t0:.2f}s, losses "
          f"{ref['loss']}")
    nums = check.numbers(got, ref)
    correct = check.verdict(nums, ck["limits"])

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": n, "failed": failed}
    tokens = n * traffic["seq_len"] * traffic["global_batch"]
    if not traced:
        e2e = {"tokens_per_s_per_chip": tokens / window_s / len(devices),
               "hbm_gib": hbm / 2 ** 30,
               "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in man.end_to_end(name)}
        result["metrics"] = {m: {"value": e2e[m], "unit": u}
                             for m, u in units.items()}
    else:
        summary = trace.Summary(trace.load(os.path.join(man.root,
                                                        TRACE_DIR)),
                                kernels=kernels)
        ctx = Context(cell=cell, config=config, traffic=traffic, dims=dims,
                      chips=len(devices), steps=n,
                      work=flops.step_work(dims, traffic["seq_len"],
                                           traffic["global_batch"]),
                      peak=peak, trace=summary)
        metrics = {}
        for m in man.per_layer(name):
            value = man.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            elif require_tpu:
                # the cell lists the metric, so the chip's trace has to
                # hold what it reads: a pattern that matches nothing
                raise RuntimeError(f"{m['name']}: its reader found nothing "
                                   "in the trace of a cell that lists it")
        result["metrics"] = metrics
        device.update(busy_s=summary.busy_s(), window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["device"] = device
    limits = ck["limits"]
    result["check"] = {k_: {"value": nums[k_], "limit": limits[k_]}
                       for k_ in check.NUMBERS if k_ in limits}
    return result, check.report_lines(nums, limits)


def main_cli(args, root: str, t_start: float) -> int:
    man = Manifest(root)
    try:
        result, lines = run(man, args.workload, args.seed, args.seconds,
                            bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr)
    return 0
