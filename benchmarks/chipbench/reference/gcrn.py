"""GCRN-M2 in plain float32 ``jax.numpy``: the graph-convolutional LSTM
of Seo et al. (arXiv:1612.07659, model 2), DGNN-Booster's V2 base model.

One snapshot G^t with normalized adjacency A (see ``refcore``), node
features X, per-edge features E (zero on self-loops):

    Xa = A (X + E W_e)            edge features enter each message
    Ha = A H^{t-1}
    [i f g o] = Xa W_x + Ha W_h + b
    c^t = sigmoid(f) c^{t-1} + sigmoid(i) tanh(g)
    h^t = sigmoid(o) tanh(c^t)
    y^t = h^t W_o + b_o           the served output, one row per node

h and c live in stores over every global node; a snapshot reads and
writes the rows of its active nodes and leaves the others as they were.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.refcore import aggregate

FAMILY = "gcrn"


def make_params(key, m: dict) -> dict:
    """Seeded weights in the program's parameter tree."""
    din, hd, de, dout = m["in_dim"], m["hidden"], m["edge_dim"], m["out_dim"]
    k = jax.random.split(key, 6)

    def glorot(kk, shape):
        return (jax.random.normal(kk, shape, jnp.float32)
                * jnp.sqrt(2.0 / (shape[0] + shape[1])))

    # small random biases (forget gate around 1) so that every bias path
    # reaches the compared outputs
    b = 0.1 * jax.random.normal(k[2], (4 * hd,), jnp.float32)
    b = b.at[hd:2 * hd].add(1.0)
    p = {"lstm": {"wx": glorot(k[0], (din, 4 * hd)),
                  "wh": glorot(k[1], (hd, 4 * hd)), "b": b},
         "head": {"w": jax.random.normal(k[3], (hd, dout), jnp.float32)
                  / jnp.sqrt(hd),
                  "b": 0.1 * jax.random.normal(k[4], (dout,), jnp.float32)}}
    if de:
        s = 1.0 / jnp.sqrt(de)
        p["w_edge"] = jax.random.uniform(k[5], (de, din), jnp.float32, -s, s)
    return p


def init_state(params: dict, m: dict, n_global: int, dot) -> dict:
    del params, dot
    z = jnp.zeros((n_global, m["hidden"]), jnp.float32)
    return {"h": z, "c": z}


def to_store(state: dict) -> dict:
    """Append the spare row that padding nodes read and write."""
    return jax.tree.map(lambda a: jnp.concatenate([a, jnp.zeros_like(a[:1])]),
                        state)


def from_store(state: dict) -> dict:
    return jax.tree.map(lambda a: a[:-1], state)


def step(params: dict, state: dict, g: dict, m: dict, dot) -> tuple:
    hd = m["hidden"]
    msg = g["x"][g["src"]]
    if "w_edge" in params:
        msg = msg + dot(g["ef"], params["w_edge"])
    xa = aggregate(g, msg)
    h_prev = state["h"][g["rows"]]
    c_prev = state["c"][g["rows"]]
    ha = aggregate(g, h_prev[g["src"]])
    lstm = params["lstm"]
    gates = dot(xa, lstm["wx"]) + dot(ha, lstm["wh"]) + lstm["b"]
    i, f, gg, o = (gates[:, j * hd:(j + 1) * hd] for j in range(4))
    c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(gg)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    out = dot(h, params["head"]["w"]) + params["head"]["b"]
    return {"h": state["h"].at[g["rows"]].set(h),
            "c": state["c"].at[g["rows"]].set(c)}, out
