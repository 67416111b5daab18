"""A kind for the tests only: GraphSAGE as the program's ``sage`` layer
serves it, ``h W_self + A_hat (h W_neigh)``, ReLU between layers.

It stands for a kind that is not GCN and is added as files alone: this
module and its reference (``reference_sage.py``), found by name."""
from __future__ import annotations

import numpy as np

from cost import aggregation_work


def layer_dims(model: dict) -> list[tuple[int, int]]:
    dims = [model["d_in"]] + [model["d_hidden"]] * (model["n_layers"] - 1) + [model["n_classes"]]
    return list(zip(dims[:-1], dims[1:]))


def weights(model: dict, key):
    """Glorot-normal ``w_self`` and ``w_neigh`` of each layer, in one
    jitted call from ``key``, in float32."""
    import jax
    import jax.numpy as jnp

    dims = layer_dims(model)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, 2 * len(dims))
        return {
            f"layer{i}": {
                name: jax.random.normal(keys[2 * i + j], (di, do), jnp.float32) * np.sqrt(2.0 / (di + do))
                for j, name in enumerate(("w_self", "w_neigh"))
            }
            for i, (di, do) in enumerate(dims)
        }

    return build(key)


def program_config(model: dict) -> dict:
    return {k: model[k] for k in ("kind", "d_in", "d_hidden", "n_classes", "n_layers")}


def host_weights(params, model: dict) -> list:
    """Each layer's ``(W_self, W_neigh)`` on the host."""
    return [(np.asarray(params[f"layer{i}"]["w_self"]), np.asarray(params[f"layer{i}"]["w_neigh"]))
            for i in range(model["n_layers"])]


def model_flops(model: dict, n: int, nnz: int) -> float:
    """Per layer two combinations ``2 n d_in d_out`` and the aggregation
    ``2 nnz d_out``."""
    return sum(4.0 * n * di * do + 2.0 * nnz * do for di, do in layer_dims(model))


def aggregations(model: dict, n: int, nnz: int) -> list[tuple[int, float, float]]:
    return [(do, *aggregation_work(n, nnz, do)) for _, do in layer_dims(model)]
