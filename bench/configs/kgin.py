"""Plain fp32 reference for the ``kgin`` configuration (``kgin.json``).

KGIN (arXiv:2102.07057) as the system under test states it, written in
``jax.numpy`` with nothing of the program imported: relational-path
aggregation with intent-weighted relation embeddings. Where this departs
from the paper (one intent-averaged relation table instead of per-user
intent attention; mean aggregation with a LeakyReLU) PERF.md says so.

    alpha   = softmax(intent)                        (P, R), over relations
    w_r     = e_r + mean_p (alpha @ E_rel)_p         (R, d)
    e'(t)   = LeakyReLU(mean over edges (h, r, t) of w_r * e_h)
    reps    = e^0 + e^1 + ... + e^L                  (sum readout)
    loss    = -mean log sigmoid(<u, i+> - <u, i->) + l2 * sum ||theta||^2
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_params(key, cfg: dict) -> dict:
    """The benchmark's own weights, in the layout the program's kgin
    step takes, ~ N(0, 0.1^2) (one draw, cut into the leaves)."""
    d, r, n = cfg["dim"], cfg["n_relation_ids"], cfg["n_nodes"]
    flat = 0.1 * jax.random.normal(key, (n * d + r * d
                                         + cfg["n_intents"] * r,))
    return {"entity": flat[:n * d].reshape(n, d),
            "relation": flat[n * d:(n + r) * d].reshape(r, d),
            "intent": flat[(n + r) * d:].reshape(cfg["n_intents"], r)}


def reps(p: dict, graph: dict, cfg: dict, dtype=jnp.float32):
    src, dst, rel = graph["src"], graph["dst"], graph["rel"]
    n = cfg["n_nodes"]
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), p)
    alpha = jax.nn.softmax(p["intent"], axis=-1)
    w = p["relation"] + jnp.mean(alpha @ p["relation"], axis=0)
    deg = jax.ops.segment_sum(jnp.ones_like(dst, dtype), dst,
                              num_segments=n)
    e = p["entity"]
    out = e
    for _ in range(cfg["n_layers"]):
        agg = jax.ops.segment_sum(e[src] * w[rel], dst, num_segments=n)
        agg = agg / jnp.maximum(deg, 1.0)[:, None]
        e = jnp.where(agg > 0, agg, 0.01 * agg)
        out = out + e
    return out, p


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
    relation-modulated aggregation (each edge element is scaled by w_r
    and summed into its destination), the intent mixing and the BPR
    scores, forward once and backward twice over, no recompute."""
    d, nl = cfg["dim"], cfg["n_layers"]
    fwd = (2 * cfg["n_intents"] * cfg["n_relation_ids"] * d
           + nl * 2 * n_edges * d
           + 2 * 2 * batch * d)
    return 3 * fwd
