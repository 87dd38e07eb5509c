"""EvolveGCN-O in plain float32 ``jax.numpy``: Pareja et al.
(arXiv:1902.10191), DGNN-Booster's V1 base model.

Each GCN layer l has a weight matrix W_l that a matrix GRU evolves once
per snapshot, taking W_l as both its input and its hidden state (the
columns of W_l are the GRU's batch):

    W_l^t = GRU_l(W_l^{t-1}),  W_l^0 the layer's parameter
    X_{l+1} = act(A (X_l + E W_e,l) W_l^t + b_l)   act = relu, none last
    y^t = X_L

The tenant's state is the list of evolved matrices, held one evolution
ahead: it starts at GRU(W_l^0), a snapshot is served with the matrices in
the state, and the state then advances by one GRU step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refcore import aggregate

FAMILY = "evolve"


def layer_dims(m: dict) -> list:
    dims, din = [], m["in_dim"]
    for layer in range(m["n_gnn_layers"]):
        dout = m["out_dim"] if layer == m["n_gnn_layers"] - 1 else m["hidden"]
        dims.append((din, dout))
        din = dout
    return dims


def make_params(key, m: dict) -> dict:
    """Seeded weights in the program's parameter tree."""
    dims = layer_dims(m)
    keys = jax.random.split(key, 6 * len(dims))
    de = m["edge_dim"]

    def glorot(kk, shape):
        return (jax.random.normal(kk, shape, jnp.float32)
                * jnp.sqrt(2.0 / (shape[0] + shape[1])))

    gcn, gru = [], []
    for layer, (din, dout) in enumerate(dims):
        k = keys[6 * layer:6 * layer + 6]
        s = 1.0 / jnp.sqrt(din)
        p = {"w": jax.random.uniform(k[0], (din, dout), jnp.float32, -s, s),
             "b": 0.1 * jax.random.normal(k[1], (dout,), jnp.float32)}
        if de:
            se = 1.0 / jnp.sqrt(de)
            p["w_edge"] = jax.random.uniform(k[2], (de, din), jnp.float32,
                                             -se, se)
        gcn.append(p)
        gru.append({"wx": glorot(k[3], (din, 3 * din)),
                    "wh": glorot(k[4], (din, 3 * din)),
                    "b": 0.1 * jax.random.normal(k[5], (3 * din,),
                                                 jnp.float32)})
    return {"gcn": gcn, "gru": gru}


def matrix_gru(g: dict, w: jax.Array, dot) -> jax.Array:
    wt = w.T
    d = wt.shape[1]
    gx = dot(wt, g["wx"]) + g["b"]
    gh = dot(wt, g["wh"])
    r = jax.nn.sigmoid(gx[:, :d] + gh[:, :d])
    z = jax.nn.sigmoid(gx[:, d:2 * d] + gh[:, d:2 * d])
    n = jnp.tanh(gx[:, 2 * d:] + r * gh[:, 2 * d:])
    return ((1.0 - z) * n + z * wt).T


def init_state(params: dict, m: dict, n_global: int, dot) -> dict:
    del m, n_global
    return {"weights": [matrix_gru(g, p["w"], dot)
                        for p, g in zip(params["gcn"], params["gru"])]}


def to_store(state: dict) -> dict:
    return state


def from_store(state: dict) -> dict:
    return state


def step(params: dict, state: dict, g: dict, m: dict, dot) -> tuple:
    del m
    x = g["x"]
    layers = params["gcn"]
    for layer, (p, w) in enumerate(zip(layers, state["weights"])):
        msg = x[g["src"]]
        if "w_edge" in p:
            msg = msg + dot(g["ef"], p["w_edge"])
        x = dot(aggregate(g, msg), w) + p["b"]
        if layer < len(layers) - 1:
            x = jax.nn.relu(x)
    weights = [matrix_gru(gp, w, dot)
               for gp, w in zip(params["gru"], state["weights"])]
    return {"weights": weights}, x
