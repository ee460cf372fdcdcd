"""Batch embedding inference with a compiled transformer (BASELINE config #4).

The flagship ML-inference pattern: wrap a jax model's forward pass as a
``Dict[str, jax.Array]`` transformer; ``transform()`` runs it as ONE
``shard_map`` across the TPU mesh — each shard computes its rows' embeddings
on its own chip, with zero per-row Python.

Run: python examples/batch_inference.py [--cpu]
(--cpu forces an 8-device virtual CPU mesh)
"""

import os
import sys

if "--cpu" in sys.argv:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax as _jax

    _jax.config.update("jax_platforms", "cpu")

# allow running the example straight from a checkout
if "__file__" in globals():
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

import fugue_tpu.api as fa

D_IN, D_HIDDEN, D_OUT = 8, 64, 4

# a stand-in encoder: in real use this is a flax/haiku model's apply fn
rng = np.random.default_rng(0)
W1 = jnp.asarray(rng.normal(size=(D_IN, D_HIDDEN)), dtype=jnp.float32)
W2 = jnp.asarray(rng.normal(size=(D_HIDDEN, D_OUT)), dtype=jnp.float32)


def embed(cols: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    x = jnp.stack([cols[f"f{i}"] for i in range(D_IN)], axis=1).astype(jnp.float32)
    h = jax.nn.relu(x @ W1)  # weights are closure constants → replicated
    e = h @ W2
    out = {"id": cols["id"]}
    for i in range(D_OUT):
        out[f"e{i}"] = e[:, i].astype(jnp.float64)
    return out


def main() -> None:
    n = 10_000
    df = pd.DataFrame({"id": np.arange(n)})
    for i in range(D_IN):
        df[f"f{i}"] = rng.normal(size=n)

    schema = "id:long," + ",".join(f"e{i}:double" for i in range(D_OUT))
    res = fa.transform(df, embed, schema=schema, engine="tpu")
    print(res.head(3))
    print(f"embedded {len(res)} rows -> {D_OUT}-dim")


if __name__ == "__main__":
    main()
