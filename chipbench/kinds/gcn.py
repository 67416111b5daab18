"""The GCN kind (Kipf and Welling 2017): everything of the benchmark that
depends on a GCN's shape.

Layer ``l`` maps ``d_in -> d_out`` as ``A_hat (h W_l)``: the program's
``gcn`` layer reads one ``{"w": W_l}`` per layer, and the reference
(``reference.py``) takes the list of ``W_l``.  Each forward runs one
aggregation per layer, at the layer's output width.
"""
from __future__ import annotations

import numpy as np

from cost import aggregation_work


def layer_dims(model: dict) -> list[tuple[int, int]]:
    """(d_in, d_out) of each layer."""
    dims = [model["d_in"]] + [model["d_hidden"]] * (model["n_layers"] - 1) + [model["n_classes"]]
    return list(zip(dims[:-1], dims[1:]))


def weights(model: dict, key):
    """Glorot-normal weights for each layer, made on the device in one
    jitted call from ``key``, in float32 (the type they are served in)."""
    import jax
    import jax.numpy as jnp

    dims = layer_dims(model)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(dims))
        return {
            f"layer{i}": {
                "w": jax.random.normal(k, (di, do), jnp.float32) * np.sqrt(2.0 / (di + do))
            }
            for i, (k, (di, do)) in enumerate(zip(keys, dims))
        }

    return build(key)


def program_config(model: dict) -> dict:
    """``GNNConfig``'s fields other than ``name`` and ``backend``."""
    return {k: model[k] for k in ("kind", "d_in", "d_hidden", "n_classes", "n_layers")}


def host_weights(params, model: dict) -> list:
    """The reference's weights: each layer's ``W`` on the host."""
    return [np.asarray(params[f"layer{i}"]["w"]) for i in range(model["n_layers"])]


def model_flops(model: dict, n: int, nnz: int) -> float:
    """Operations of one forward over a graph: per layer the combination
    ``2 n d_in d_out`` and the aggregation ``2 nnz d_out``."""
    return sum(2.0 * n * di * do + 2.0 * nnz * do for di, do in layer_dims(model))


def aggregations(model: dict, n: int, nnz: int) -> list[tuple[int, float, float]]:
    """(width, flops, bytes) of each aggregation of one forward, in order."""
    return [(do, *aggregation_work(n, nnz, do)) for _, do in layer_dims(model)]
