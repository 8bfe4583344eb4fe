"""The reference split over four virtual CPU devices trains as it does on
one: prints the largest relative gap of its readings.

    python bench/tests/_mesh_reference.py
"""
import json
import os
import sys

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import conftest  # noqa: F401  (paths)

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from bench import reference as R
    from bench.traffic import Stream
    from bench.weights import Dims, seed_key

    R.Q_BLOCK, R.MLP_CHUNK, R.LOSS_CHUNK = 64, 128, 128
    with open(os.path.join(conftest.REPO, "bench", "configs",
                           "qwen3-1.7b.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=8, num_key_value_heads=4, head_dim=16,
               vocab_size=512)
    dims = Dims.from_config(cfg)
    with open(os.path.join(conftest.REPO, "bench", "traffic",
                           "s32k.json")) as f:
        opt = R.Opt(**json.load(f)["optimizer"])
    st = Stream({"kind": "affine_stream", "seq_len": 256, "global_batch": 1,
                 "noise": 0.1}, 512, 9)
    batches = [st.logical(i) for i in range(3)]
    one = R.train(dims, opt, seed_key(3), batches)
    mesh = Mesh(np.array(jax.devices()[:4]), ("t",))
    four = R.train(dims, opt, seed_key(3), batches, mesh=mesh)
    gap = max(abs(a - b) / abs(b) for a, b in zip(four["loss"], one["loss"]))
    for k in ("grad", "change"):
        gap = max(gap, max(abs(four[k][n] - one[k][n]) / max(one[k][n], 1e-12)
                           for n in one[k]))
    print(json.dumps({"gap": gap}))
