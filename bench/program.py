"""The system under test, as the benchmark drives it.

This is the one module of the benchmark that imports the program.  It
builds the program's own objects (``build_plan`` -> ``Trainer``) for a
cell, gives them the benchmark's weights, and reads back what
the comparison needs.  The step is compiled once, ahead of the first
call, so that its memory analysis can be read; that compiled step, the
trainer around it and the trainer's own data source are what both the
first checked steps and the measured window drive, through
``Trainer.run()``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.trace import kernel_names
from bench.traffic import Stream
from bench.weights import Dims, init, leaf_norms, seed_key
from repro.configs import get_config
from repro.core.plan import build_plan
from repro.core.topology import ParallelConfig
from repro.core.zigzag import zigzag_indices
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer, TrainerConfig

_NORMS = {"rmsnorm": "rms", "layernorm_nonparametric": "ln_np"}


def model_config(config: dict, dims: Dims):
    """The program's ModelConfig for a configuration file: the program's
    execution settings for the architecture, every size from the file."""
    return dataclasses.replace(
        get_config(config["program_arch"]), num_layers=dims.layers,
        d_model=dims.d_model, n_heads=dims.n_heads,
        n_kv_heads=dims.n_kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab=dims.vocab, qk_norm=dims.qk_norm, rope_theta=dims.rope_theta,
        norm=_NORMS[dims.norm], act="silu", tie_embeddings=True,
        dtype=config["precision"]["compute"])


def to_program(w: dict, dims: Dims) -> dict:
    """The benchmark's weights in the program's layout.  The program's
    RMSNorm scales by ``1 + w``, so a gain ``g`` is stored as ``g - 1``."""
    L = w["layers"]
    rms = dims.norm == "rmsnorm"
    gain = lambda g: {"w": g - 1.0}
    attn = {k: {"w": L[k]} for k in ("wq", "wk", "wv", "wo")}
    if dims.qk_norm:
        attn["qn"], attn["kn"] = gain(L["q_norm"]), gain(L["k_norm"])
    block = {"ln1": gain(L["attn_norm"]) if rms else {},
             "ln2": gain(L["mlp_norm"]) if rms else {},
             "attn": attn,
             "mlp": {k: {"w": L[k]} for k in ("w1", "w3", "w2")}}
    return {"embed": {"table": w["embed"]},
            "final_norm": gain(w["final_norm"]) if rms else {},
            "blocks": [block]}


def from_program(p: dict, dims: Dims) -> dict:
    """A tree in the program's layout (gradients, moments, differences of
    weights) back in the benchmark's layout, leaf for leaf, unshifted."""
    b = p["blocks"][0]
    layers = {k: b["attn"][k]["w"] for k in ("wq", "wk", "wv", "wo")}
    layers.update({k: b["mlp"][k]["w"] for k in ("w1", "w3", "w2")})
    if dims.norm == "rmsnorm":
        layers["attn_norm"], layers["mlp_norm"] = b["ln1"]["w"], b["ln2"]["w"]
        if dims.qk_norm:
            layers["q_norm"] = b["attn"]["qn"]["w"]
            layers["k_norm"] = b["attn"]["kn"]["w"]
    out = {"embed": p["embed"]["table"], "layers": layers}
    if dims.norm == "rmsnorm":
        out["final_norm"] = p["final_norm"]["w"]
    return out


class Expected:
    """The batches the program's data source has to give: the benchmark's
    stream in the program's layout (zigzag over the context ranks, then the
    microbatch split), indexed by step."""

    def __init__(self, stream: Stream, data_cfg):
        s = stream.seq
        self.stream = stream
        self.perm = (zigzag_indices(s, data_cfg.cp)
                     if data_cfg.zigzag and data_cfg.cp > 1 else np.arange(s))
        self.accum = data_cfg.grad_accum
        self.positions = np.broadcast_to(np.arange(s, dtype=np.int32),
                                         (stream.batch, s))

    def _layout(self, a):
        a = a[:, self.perm]
        if self.accum > 1:
            a = a.reshape((self.accum, a.shape[0] // self.accum) + a.shape[1:])
        return a

    def batch(self, step: int) -> dict:
        tokens, labels = self.stream.logical(step)
        return {"tokens": self._layout(tokens),
                "labels": self._layout(labels),
                "positions": self._layout(self.positions)}


class Program:
    """The program's trainer for one cell, with the benchmark's weights,
    its own data source and its step compiled."""

    def __init__(self, config: dict, traffic: dict, dims: Dims, devices,
                 seed: int):
        self.dims = dims
        self.opt = OptConfig(**traffic["optimizer"])
        seq, gb = traffic["seq_len"], traffic["global_batch"]
        self.plan = build_plan(model_config(config, dims),
                               ParallelConfig(**traffic["layout"]), self.opt,
                               devices=devices, seq_len=seq, global_batch=gb)
        data_cfg = self.plan.data_config(seq, gb, seed=seed)
        tr = Trainer(self.plan, data_cfg, TrainerConfig(num_steps=0))
        # the trainer's own source (SyntheticLM) feeds every step; the
        # benchmark's stream only says what it has to give
        self.source = tr.data
        self.expected = Expected(Stream(traffic, dims.vocab, seed), data_cfg)
        with self.plan.mesh:
            tr.params = jax.jit(lambda k: to_program(init(dims, k), dims),
                                out_shardings=tr.p_sh)(seed_key(seed))
        self.key = seed_key(seed)
        with self.plan.mesh:
            compiled = tr.step_fn.lower(tr.params, tr.opt_state,
                                        self.expected.batch(0)).compile()
        self.memory = compiled.memory_analysis()
        #: {HLO instruction: Pallas kernel} of the compiled step
        self.kernels = kernel_names(compiled.as_text())
        tr.step_fn = compiled
        self.trainer = tr

    def hbm_bytes(self) -> int:
        """The step's device memory: arguments + outputs + temporaries -
        aliased, as compiled for one device of the mesh."""
        m = self.memory
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)

    def run(self, start: int, stop: int) -> list[float]:
        """Steps ``start .. stop-1`` through ``Trainer.run()``."""
        tr = self.trainer
        tr.start_step, tr.tcfg.num_steps = start, stop
        return tr.run()

    def sync(self):
        jax.block_until_ready((self.trainer.params, self.trainer.opt_state))

    def data_gaps(self, k: int) -> int:
        """Entries in which the program's source's batches for steps
        ``0 .. k-1`` (a function of the seed and the step) differ from the
        benchmark's stream."""
        gaps = 0
        for step in range(k):
            got, want = self.source.batch(step), self.expected.batch(step)
            gaps += sum(int(np.sum(np.asarray(got[n]) != want[n]))
                        if np.shape(got[n]) == np.shape(want[n])
                        else int(np.size(want[n])) for n in want)
        return gaps

    def first_grad(self) -> dict:
        """After exactly one step: per leaf, the norm of the gradient the
        optimizer got, from its first moment ``(1 - b1) * g * clip``."""
        gn = self.trainer.history[-1]["grad_norm"]
        clip = min(1.0, self.opt.clip_norm / (gn + 1e-12))
        dims = self.dims
        with self.plan.mesh:
            norms = jax.jit(lambda m: leaf_norms(from_program(m, dims)))(
                self.trainer.opt_state["m"])
        div = (1.0 - self.opt.beta1) * clip
        return {k: float(v) / div for k, v in norms.items()}

    def change(self) -> dict:
        """Per leaf, the norm of the weights' change since the start."""
        dims = self.dims

        def f(p, key):
            p0 = to_program(init(dims, key), dims)
            d = jax.tree.map(jnp.subtract, p, p0)
            return leaf_norms(from_program(d, dims))

        with self.plan.mesh:
            norms = jax.jit(f)(self.trainer.params, self.key)
        return {k: float(v) for k, v in norms.items()}

    def close(self):
        """Drop the program's state so that its memory is free."""
        tr = self.trainer
        tr.guard.uninstall()
        self.trainer = tr.params = tr.opt_state = tr.step_fn = None
