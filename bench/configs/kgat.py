"""Plain fp32 reference for the ``kgat`` configuration (``kgat.json``).

KGAT (arXiv:1905.07854) over the collaborative knowledge graph, written
straight from its equations in ``jax.numpy``: no ACT, no kernels, no
views. It imports nothing of the program. Two departures from the paper
follow the system under test and are noted in PERF.md: the relation
projection is W_r = sum_b a_rb V_b over ``n_bases`` shared bases, and the
attention is computed once, from the layer-0 embeddings.

    pi(h, r, t) = (W_r e_t) . tanh(W_r e_h + e_r), softmax over t's edges
    e_N(t)      = sum over edges (h, r, t) of pi * e_h
    e'          = LeakyReLU(W1 (e + e_N)) + LeakyReLU(W2 (e * e_N))
    reps        = [e^0 | e^1 | ... | e^L]                  (concat readout)
    loss        = -mean log sigmoid(<u, i+> - <u, i->) + l2 * sum ||theta||^2
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def init_params(key, cfg: dict) -> dict:
    """The benchmark's own weights, in the layout the program's kgat
    step takes: tables ~ N(0, 0.1^2), layer weights Glorot-uniform
    (one normal and one uniform draw, cut into the leaves)."""
    d, nb, nl = cfg["dim"], cfg["n_bases"], cfg["n_layers"]
    r = cfg["n_relation_ids"]
    shapes = [(cfg["n_nodes"], d), (r, d), (nb, d, d), (r, nb)]
    kn, ku = jax.random.split(key)
    flat = 0.1 * jax.random.normal(kn, (sum(math.prod(s) for s in shapes),))
    tables, at = [], 0
    for s in shapes:
        tables.append(flat[at:at + math.prod(s)].reshape(s))
        at += math.prod(s)
    lim = (6.0 / (2 * d)) ** 0.5
    w = jax.random.uniform(ku, (2 * nl, d, d), jnp.float32, -lim, lim)
    return {"entity": tables[0], "relation": tables[1],
            "att_basis": tables[2], "att_coef": tables[3],
            "w1": [w[i] for i in range(nl)],
            "w2": [w[nl + i] for i in range(nl)]}


def _leaky(x):
    return jnp.where(x > 0, x, 0.01 * x)


def reps(p: dict, graph: dict, cfg: dict, dtype=jnp.float32):
    src, dst, rel = graph["src"], graph["dst"], graph["rel"]
    n = cfg["n_nodes"]
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), p)
    e = p["entity"]
    coef = p["att_coef"][rel]                                  # (E, B)
    eh = et = 0.0
    for b in range(cfg["n_bases"]):
        proj = e @ p["att_basis"][b]                           # (N, d)
        eh = eh + coef[:, b:b + 1] * proj[src]
        et = et + coef[:, b:b + 1] * proj[dst]
    logits = jnp.sum(et * jnp.tanh(eh + p["relation"][rel]), axis=-1)
    mx = jax.ops.segment_max(logits, dst, num_segments=n)
    ex = jnp.exp(logits - mx[dst])
    den = jax.ops.segment_sum(ex, dst, num_segments=n)
    att = ex / (den[dst] + 1e-16)
    outs = [e]
    for l in range(cfg["n_layers"]):
        e_n = jax.ops.segment_sum(e[src] * att[:, None], dst, num_segments=n)
        e = _leaky((e + e_n) @ p["w1"][l]) + _leaky((e * e_n) @ p["w2"][l])
        outs.append(e)
    return jnp.concatenate(outs, axis=-1), p


def loss(params: dict, graph: dict, batch: dict, cfg: dict,
         dtype=jnp.float32):
    r, p = reps(params, graph, cfg, dtype)
    u = r[batch["user"]]
    pos = jnp.sum(u * r[cfg["n_users"] + batch["pos"]], axis=-1)
    neg = jnp.sum(u * r[cfg["n_users"] + batch["neg"]], axis=-1)
    bpr = -jnp.mean(jax.nn.log_sigmoid(pos - neg))
    reg = sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(p))
    return (bpr + cfg["l2"] * reg).astype(jnp.float32)


def train_flops(cfg: dict, *, n_nodes: int, n_edges: int, batch: int) -> int:
    """Operations one training step requires: 2 per multiply-add of the
    contractions above (basis projections, per-edge basis mixing and
    attention logit, weighted aggregation, W1/W2, BPR scores), forward
    once and backward twice over, no recompute. Elementwise work is not
    counted."""
    d, nb, nl = cfg["dim"], cfg["n_bases"], cfg["n_layers"]
    width = d * (nl + 1)
    fwd = (2 * nb * n_nodes * d * d          # e @ V_b
           + 2 * 2 * n_edges * nb * d        # head and tail basis mixing
           + 2 * n_edges * d                 # attention logit
           + nl * (2 * n_edges * d           # weighted aggregation
                   + 2 * 2 * n_nodes * d * d)  # W1, W2
           + 2 * 2 * batch * width)          # positive and negative scores
    return 3 * fwd
