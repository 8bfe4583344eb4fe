"""Model sizes from a configuration file, and the benchmark's own weights.

Weights are made from ``--seed`` by the benchmark, in one layout of its own
(below), on the device, in float32: the type the program keeps its masters
in.  The program gets them through ``bench/program.py``'s adapter; the
reference (``bench/reference.py``) makes them again from the same seed.
Nothing here imports the program.

Layout: ``embed`` (V, D); ``layers``: per weight kind one array stacked
over layers, ``wq`` (L, D, Hq*hd), ``wk``/``wv`` (L, D, Hkv*hd), ``wo``
(L, Hq*hd, D), ``w1``/``w3`` (L, D, F), ``w2`` (L, F, D), and for RMSNorm
models the gains ``attn_norm``/``mlp_norm`` (L, D), ``q_norm``/``k_norm``
(L, hd); ``final_norm`` (D,) for RMSNorm models.  Gains multiply the
normalized activation (``x * g``) and start at 1; matrices are normal with
the configuration's ``initializer_range``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w1", "w3", "w2")
LAYER_GAINS = ("attn_norm", "mlp_norm")
HEAD_GAINS = ("q_norm", "k_norm")


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "rmsnorm" | "layernorm_nonparametric"
    norm_eps: float
    qk_norm: bool
    rope_theta: float
    init_std: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if c.get("hidden_act") != "silu" or not c.get("tie_word_embeddings"):
            raise ValueError("only SwiGLU decoders with a tied head are "
                             "described here")
        heads = c["num_attention_heads"]
        norm = c["norm"]
        eps = c["rms_norm_eps"] if norm == "rmsnorm" else c["norm_eps"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=heads, n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   norm=norm, norm_eps=float(eps),
                   qk_norm=bool(c.get("qk_norm", False)),
                   rope_theta=float(c["rope_theta"]),
                   init_std=float(c["initializer_range"]))

    @property
    def gains(self) -> tuple:
        if self.norm != "rmsnorm":
            return ()
        return LAYER_GAINS + (HEAD_GAINS if self.qk_norm else ())

    def shapes(self) -> dict:
        """Leaf shapes of the benchmark's layout."""
        L, D, F = self.layers, self.d_model, self.d_ff
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        layers = {"wq": (L, D, q), "wk": (L, D, kv), "wv": (L, D, kv),
                  "wo": (L, q, D), "w1": (L, D, F), "w3": (L, D, F),
                  "w2": (L, F, D)}
        for g in self.gains:
            layers[g] = (L, self.head_dim if g in HEAD_GAINS else D)
        out = {"embed": (self.vocab, D), "layers": layers}
        if self.norm == "rmsnorm":
            out["final_norm"] = (D,)
        return out


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative whole ``seed`` (wider than 32 bits
    too) and a stream number, through numpy's SeedSequence."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def init(dims: Dims, key) -> dict:
    """The benchmark's weights (float32).  Call under ``jax.jit``."""
    shapes = dims.shapes()
    names = ["embed"] + [f"layers.{k}" for k in sorted(shapes["layers"])]
    keys = dict(zip(names, jax.random.split(key, len(names))))

    def normal(name, shape):
        return dims.init_std * jax.random.normal(keys[name], shape,
                                                 jnp.float32)

    out = {"embed": normal("embed", shapes["embed"]), "layers": {}}
    for k, shape in shapes["layers"].items():
        out["layers"][k] = (jnp.ones(shape, jnp.float32) if k in dims.gains
                            else normal(f"layers.{k}", shape))
    if "final_norm" in shapes:
        out["final_norm"] = jnp.ones(shapes["final_norm"], jnp.float32)
    return out


def leaf_norms(tree: dict) -> dict:
    """{leaf name: L2 norm}: stacked layer leaves split per layer
    (``wq.0``, ``wq.1``, ...).  Call under ``jax.jit``."""
    out = {"embed": jnp.linalg.norm(tree["embed"].astype(jnp.float32))}
    if "final_norm" in tree:
        out["final_norm"] = jnp.linalg.norm(tree["final_norm"])
    for k, a in tree["layers"].items():
        per = jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                               axis=tuple(range(1, a.ndim))))
        for i in range(a.shape[0]):
            out[f"{k}.{i}"] = per[i]
    return out
