"""`paper_mlp`: dense ReLU layers of `d_ff` units (`n_layers` of them) over
`d_model` input features, then a linear layer to `n_classes` logits, mean
cross-entropy loss. Written from the model's definition; nothing of the
program is imported.

Configuration keys: `d_model`, `d_ff`, `n_layers`, `n_classes`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def arch_fields(cfg: dict) -> dict:
    """The program's `ArchConfig` fields this configuration sets."""
    return {"d_model": cfg["d_model"], "d_ff": cfg["d_ff"],
            "n_layers": cfg["n_layers"], "vocab_size": cfg["n_classes"]}


def leaf_shapes(cfg: dict) -> list[tuple]:
    """Parameter shapes, layer by layer (weight, bias)."""
    dims = [cfg["d_model"]] + [cfg["d_ff"]] * cfg["n_layers"] \
        + [cfg["n_classes"]]
    out = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        out += [(d_in, d_out), (d_out,)]
    return out


def init_params(key, cfg: dict) -> dict:
    """Parameters drawn on the device in one jitted call: dense weights
    N(0, 1/fan_in), zero biases; the program's tree layout."""
    dims = [cfg["d_model"]] + [cfg["d_ff"]] * cfg["n_layers"]

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, cfg["n_layers"] + 1)

        def dense(k, d_in, d_out):
            return (jax.random.normal(k, (d_in, d_out), jnp.float32)
                    / np.sqrt(d_in))

        layers = [{"w": dense(keys[i], dims[i], dims[i + 1]),
                   "b": jnp.zeros((dims[i + 1],), jnp.float32)}
                  for i in range(cfg["n_layers"])]
        return {"layers": layers,
                "out": {"w": dense(keys[-1], dims[-1], cfg["n_classes"]),
                        "b": jnp.zeros((cfg["n_classes"],), jnp.float32)}}

    return draw(key)


def loss(params, x, y, mm):
    """Mean cross-entropy of a minibatch; `mm` is the matrix product."""
    h = x
    for lp in params["layers"]:
        h = jax.nn.relu(mm(h, lp["w"]) + lp["b"])
    logits = mm(h, params["out"]["w"]) + params["out"]["b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)
