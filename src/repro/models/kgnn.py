"""Knowledge Graph Neural Networks (the paper's evaluation targets, §4.1.2).

Implements the three baselines TinyKG is evaluated on — KGAT, KGCN/KGNN-LS,
KGIN — plus R-GCN, over a collaborative knowledge graph (CKG): users, items
and attribute entities are one node space; user-item interactions are
`interact` relations merged with the item KG (paper §3.1).

Message passing defaults to ``jax.ops.segment_sum`` over COO edge lists,
with a blocked-CSR fused-Pallas path (``repro.data.csr`` + DESIGN.md §4)
under ``kernel="pallas"`` policies, and is ACT-compressed end-to-end:

  * ``act_spmm``    — weighted neighbor aggregation; saves Quant(E^(l))
  * ``act_matmul``  — layer transform ∇Θ = Ĥᵀ∇J; saves Quant(H^(l))
  * ``act_nonlin``  — σ(J); saves Quant(J^(l))

which is exactly the ctx(·) chain in paper Eq. (2). Edge-softmax
probabilities are (E,)-scalars (no feature dim) and stay fp32 — they are
O(E) not O(N·d), i.e. the "trivial" footprint class of the paper's
memory analysis.

Every op site carries a named scope (``"kgat/layer2/spmm"``): the ambient
``ActContext`` resolves its per-site policy from a ``PolicySchedule`` and
derives its stochastic-rounding key from the scope hash (DESIGN.md §6).
The same names, plus ``attention`` (kgat's edge weights), ``spmm`` (kgin's
aggregation) and ``bpr`` (the head), are ``jax.named_scope``s, so compiled
ops carry their site in ``op_name`` (DESIGN.md §13).

**One step definition per arch** (DESIGN.md §9): every model's layer math
is written ONCE against a ``GraphView`` — ``FullGraphView`` for the
single-device COO path, ``ShardGraphView`` for the dst-partitioned
``shard_map`` path (``repro.training.data_parallel``). The view supplies
the gatherable source-side table (identity vs all-gather + halo shrink),
pad-edge masking (identity vs mask), and local destination rows; the
layer functions (``_kgat_layer`` …) and edge-weight functions are shared
verbatim, so the DP parity contracts rest on both paths running THIS
code rather than a hand-inlined copy.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import (
    ACTPolicy,
    FP32,
    PolicySchedule,
    act_matmul,
    act_nonlin,
    act_spmm,
    model_context,
)
from .layers import glorot, normal_init

__all__ = [
    "KGNNConfig", "CKG", "segment_softmax", "kgat_bi_interaction",
    "init_params", "propagate", "score_pairs", "bpr_loss",
    "FullGraphView", "ShardGraphView", "Shard2DGraphView", "BlockView",
    "SampledGraphView",
    "model_sites", "propagate_view", "kg_shard_loss", "readout",
    "sampled_bpr_loss", "sampled_reps",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CKG:
    """Collaborative knowledge graph in COO form (inverse edges included).

    ``n_nodes``/``n_relations`` are pytree aux data — static under jit
    (segment_sum needs static segment counts). ``layout`` optionally
    carries the blocked-CSR arrangement of the same edge list
    (``repro.data.csr.attach_layout``) that routes ``act_spmm`` through
    the fused Pallas kernels under ``ACTPolicy(kernel="pallas")``.
    """

    src: jax.Array  # (E,) int32 node ids
    dst: jax.Array  # (E,) int32 node ids
    rel: jax.Array  # (E,) int32 relation ids
    n_nodes: int    # users + entities (static)
    n_relations: int
    layout: object | None = None  # SpmmLayout (itself a pytree) or None

    def tree_flatten(self):
        return (self.src, self.dst, self.rel, self.layout), (
            self.n_nodes, self.n_relations)

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, dst, rel, layout = children
        return cls(src, dst, rel, aux[0], aux[1], layout)


@dataclasses.dataclass(frozen=True)
class KGNNConfig:
    model: str = "kgat"          # kgat | kgcn | kgin | rgcn
    n_users: int = 0
    n_entities: int = 0          # items + attribute entities
    n_relations: int = 0         # incl. `interact`, both directions
    dim: int = 64                # embedding size (paper fixes 64)
    n_layers: int = 3            # paper fixes 3
    layer_dims: tuple = ()       # per-layer out dims; default = dim each
    n_intents: int = 4           # KGIN
    n_bases: int = 4             # R-GCN basis decomposition
    l2: float = 1e-5
    readout: str = "concat"      # concat (KGAT) | sum (KGIN) | last

    @property
    def n_nodes(self) -> int:
        return self.n_users + self.n_entities

    @property
    def dims(self) -> tuple:
        return self.layer_dims or (self.dim,) * self.n_layers


def segment_softmax(logits: jax.Array, seg: jax.Array, num_segments: int):
    """Numerically-stable softmax over segments (edge softmax)."""
    mx = jax.ops.segment_max(logits, seg, num_segments=num_segments)
    ex = jnp.exp(logits - mx[seg])
    den = jax.ops.segment_sum(ex, seg, num_segments=num_segments)
    return ex / (den[seg] + 1e-16)


# ---------------------------------------------------------------------------
# graph views: one set of layer functions, three execution layouts
# ---------------------------------------------------------------------------


class _ViewDefaults:
    """Hooks every view shares; identity for the whole-graph views.

    The sampled-minibatch path (``SampledGraphView``) is the only one
    that overrides them: its edge set *changes per layer* (per-hop
    fanout blocks) and its row space *shrinks toward the seeds*, so the
    shared layer functions ask the view instead of assuming one static
    edge list. On ``FullGraphView``/``ShardGraphView`` every hook
    returns its argument unchanged — the jaxpr is identical to the
    pre-hook code, which the pinned bit-exact step regression relies on.
    """

    def layer_view(self, layer: int):
        """The view layer ``layer`` aggregates over (self for the
        whole-graph views; hop block ``layer`` for the sampled view)."""
        return self

    def layer_weights(self, weights, layer: int):
        """Slice the once-computed edge-weight data for one layer."""
        return weights

    def self_rows(self, e):
        """Restrict a source-row table to this layer's destination rows
        (the self/residual term of kgat/kgcn/rgcn)."""
        return e

    def seed_rows(self, e):
        """Restrict a layer output to the rows the readout keeps."""
        return e

    def param_l2(self, params):
        """Full-model L2 of the parameter pytree as this view sees it.

        Every view but the 2D mesh view sums leaves directly; the 2D
        view holds row-sharded tables as model-axis blocks and must
        psum their sum-of-squares so each shard sees the same scalar
        the replicated path would.
        """
        return sum(jnp.sum(x ** 2)
                   for x in jax.tree_util.tree_leaves(params))


@dataclasses.dataclass(frozen=True)
class FullGraphView(_ViewDefaults):
    """The whole COO graph on one device — every hook is the identity.

    ``src`` indexes the table returned by ``table`` (== the node table
    itself), ``dst`` indexes local rows (== all rows), no pad edges.
    """

    g: CKG

    @property
    def src(self):
        return self.g.src

    @property
    def dst(self):
        return self.g.dst

    @property
    def rel(self):
        return self.g.rel

    @property
    def num_rows(self) -> int:
        return self.g.n_nodes

    @property
    def layout(self):
        return self.g.layout

    def local_rows(self, table):
        return table

    def table(self, x, axis: int = 0):
        return x

    def unshard(self, x, axis: int = 0):
        return x

    def mask_logits(self, logits):
        return logits

    def mask_weights(self, w):
        return w

    def mask_messages(self, m):
        return m

    def edge_ones(self, dtype):
        return jnp.ones_like(self.g.dst, dtype=dtype)


@dataclasses.dataclass(frozen=True)
class ShardGraphView(_ViewDefaults):
    """One shard of a dst-partitioned graph, inside a ``shard_map`` body.

    Built from one row of ``repro.data.csr.EdgePartition``: ``src`` is
    halo-LOCAL (indexes the ``(h_cap, d)`` table ``table`` returns after
    the all-gather + halo shrink), ``dst`` is shard-local, pad edges
    carry ``mask == 0``. ``local_rows`` slices this shard's rows out of
    a replicated node table (pad-extended to ``n_nodes_padded``).
    """

    src: jax.Array        # (Ec,) halo-local source index
    dst: jax.Array        # (Ec,) local dst row
    rel: jax.Array        # (Ec,)
    mask: jax.Array       # (Ec,) 1=real edge, 0=pad
    halo: jax.Array       # (Hc,) unique global src ids for this shard
    axis: str             # mesh axis name
    num_rows: int         # rows per shard
    n_nodes_padded: int   # num_rows * n_shards
    layout = None         # blocked-CSR stays single-device (DESIGN.md §7.4)

    @classmethod
    def from_shard(cls, sh: dict, *, axis: str, num_rows: int,
                   n_nodes_padded: int) -> "ShardGraphView":
        return cls(src=sh["src_h"], dst=sh["dst_l"], rel=sh["rel"],
                   mask=sh["mask"], halo=sh["halo"], axis=axis,
                   num_rows=num_rows, n_nodes_padded=n_nodes_padded)

    def local_rows(self, table):
        pad = jnp.pad(table, ((0, self.n_nodes_padded - table.shape[0]),
                              (0, 0)))
        i = jax.lax.axis_index(self.axis)
        return jax.lax.dynamic_slice_in_dim(pad, i * self.num_rows,
                                            self.num_rows)

    def table(self, x, axis: int = 0):
        full = jax.lax.all_gather(x, self.axis, axis=axis, tiled=True)
        return jnp.take(full, self.halo, axis=axis)

    def unshard(self, x, axis: int = 0):
        return jax.lax.all_gather(x, self.axis, axis=axis, tiled=True)

    def mask_logits(self, logits):
        return jnp.where(self.mask > 0, logits, -1e30)

    def mask_weights(self, w):
        return w * self.mask

    def mask_messages(self, m):
        return m * self.mask[:, None]

    def edge_ones(self, dtype):
        return self.mask.astype(dtype)


@dataclasses.dataclass(frozen=True)
class Shard2DGraphView(ShardGraphView):
    """A ``ShardGraphView`` whose embedding tables are row-sharded over
    a second mesh axis (the 2D ``data×model`` mesh, DESIGN.md §12).

    Row-sharded parameters (``row_sharded``, e.g. ``"entity"``) enter
    the ``shard_map`` body as ``(table_rows, d)`` model-axis blocks
    instead of replicated ``(N, d)`` tables. Only two hooks differ from
    the 1D view:

      * ``local_rows`` — the data shard's dst rows are the contiguous
        global ids ``[s*num_rows, (s+1)*num_rows)``; ``fetch_rows``
        assembles them from the model-axis blocks (one psum), pulling
        exactly the rows this shard's edges touch. Since each fetched
        value is one real row plus zeros, the result is bit-exact
        against slicing a replicated table — so everything downstream
        (halo gathers over the data axis, layer math, ``unshard``) is
        byte-for-byte the 1D computation.
      * ``param_l2`` — sharded tables contribute through
        ``rowshard_l2`` (a psum of block sums) so the regularizer is
        the full-table L2 on every shard.

    Everything after the fetch must stay replicated over the model
    axis; the custom VJPs of both ops rely on that contract (their
    backward passes are local reduce-scatter shares).
    """

    model_axis: str = "model"
    table_rows: int = 0    # block rows per model shard
    n_valid_rows: int = 0  # real node count; padded ids fetch as zero
    row_sharded: tuple = ()  # top-level param names stored as blocks

    @classmethod
    def from_shard2d(cls, sh: dict, *, axis: str, num_rows: int,
                     n_nodes_padded: int, model_axis: str, table_rows: int,
                     n_valid_rows: int, row_sharded: tuple):
        return cls(src=sh["src_h"], dst=sh["dst_l"], rel=sh["rel"],
                   mask=sh["mask"], halo=sh["halo"], axis=axis,
                   num_rows=num_rows, n_nodes_padded=n_nodes_padded,
                   model_axis=model_axis, table_rows=table_rows,
                   n_valid_rows=n_valid_rows,
                   row_sharded=tuple(row_sharded))

    def local_rows(self, table):
        from repro.sharding.rowshard import fetch_rows

        s = jax.lax.axis_index(self.axis)
        ids = s * self.num_rows + jnp.arange(self.num_rows)
        return fetch_rows(table, ids, axis=self.model_axis,
                          rows_per_shard=self.table_rows,
                          n_valid=self.n_valid_rows)

    def param_l2(self, params):
        from repro.sharding.rowshard import rowshard_l2

        total = 0.0
        for name, sub in params.items():
            if name in self.row_sharded:
                total = total + rowshard_l2(sub, axis=self.model_axis)
            else:
                total = total + sum(jnp.sum(x ** 2)
                                    for x in jax.tree_util.tree_leaves(sub))
        return total


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockView(_ViewDefaults):
    """One sampled fanout hop: a bipartite edge block, view-shaped.

    Built host-side by ``repro.data.minibatch.sample_kg_blocks``. Local
    indexing rides on the *seeds-prefix invariant*: the hop's
    destination frontier is a prefix of its source frontier (which is a
    prefix of the outermost gathered node set), so

      * ``src`` indexes the CURRENT layer input table (``n_src`` rows),
      * ``dst`` indexes the same table's first ``n_dst`` rows,
      * both remain valid positions into the outermost layer-0 table —
        which is what lets per-hop KGAT/KGCN edge weights be computed
        once from the layer-0 embeddings, exactly like the full-graph
        semantics.

    ``mask`` zeroes pad edges (zero-degree destinations); ``layout`` is
    an optional static-geometry blocked-CSR ``SpmmLayout`` over the
    SAME slot order, so the fused Pallas SPMM runs unchanged on the
    sampled subgraph. ``n_src``/``n_dst`` are pytree aux data — static
    under jit, so a stream of same-shape blocks never retraces.
    """

    src: jax.Array        # (Eb,) block-local source index
    dst: jax.Array        # (Eb,) block-local destination index (< n_dst)
    rel: jax.Array        # (Eb,) relation ids
    mask: jax.Array       # (Eb,) 1=real sampled edge, 0=pad
    layout: object | None  # SpmmLayout over this block's edges, or None
    n_src: int            # static source-frontier size
    n_dst: int            # static destination-frontier size

    def tree_flatten(self):
        return (self.src, self.dst, self.rel, self.mask, self.layout), (
            self.n_src, self.n_dst)

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, dst, rel, mask, layout = children
        return cls(src, dst, rel, mask, layout, *aux)

    @property
    def num_rows(self) -> int:
        return self.n_dst

    def local_rows(self, table):
        return table

    def table(self, x, axis: int = 0):
        return x

    def unshard(self, x, axis: int = 0):
        return x

    def mask_logits(self, logits):
        return jnp.where(self.mask > 0, logits, -1e30)

    def mask_weights(self, w):
        return w * self.mask

    def mask_messages(self, m):
        return m * self.mask[:, None]

    def edge_ones(self, dtype):
        return self.mask.astype(dtype)

    def self_rows(self, e):
        return e[: self.n_dst]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SampledGraphView(_ViewDefaults):
    """Neighbor-sampled minibatch: one ``BlockView`` per layer.

    ``blocks[l]`` is the hop layer ``l`` consumes (outermost hop first —
    the layer-0 aggregation reads the largest frontier); the innermost
    hop's destination set is exactly the seed set, whose first
    ``n_seeds`` rows the readout keeps. ``params["entity"]`` is expected
    to ALREADY be the gathered outermost row table — the tier cache
    (``repro.training.tiering``) resolves global entity ids to rows
    before the jitted step, so ``local_rows`` is the identity and the
    step never sees the full table.
    """

    blocks: tuple         # (BlockView, ...) one per layer, outermost first
    n_seeds: int          # rows of every hop frontier that are seeds

    def tree_flatten(self):
        return (self.blocks,), (self.n_seeds,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    @property
    def n_input_rows(self) -> int:
        """Rows of the gathered entity table the step expects."""
        return self.blocks[0].n_src

    def layer_view(self, layer: int):
        return self.blocks[layer]

    def layer_weights(self, weights, layer: int):
        # kgat/kgcn: per-hop edge weights (a tuple); kgin: the
        # hop-independent intent-weighted relation table; rgcn: None
        return weights[layer] if isinstance(weights, tuple) else weights

    def local_rows(self, table):
        return table

    def seed_rows(self, e):
        return e[: self.n_seeds]


def model_sites(cfg: KGNNConfig) -> tuple[tuple[str, str], ...]:
    """Per-layer ``(site_name, op_kind)`` table for a model — the ACT
    sites a data-parallel step must pre-resolve outside ``shard_map``."""
    if cfg.model == "kgat":
        return (("spmm", "spmm"), ("w1", "matmul"), ("w2", "matmul"),
                ("act1", "nonlin"), ("act2", "nonlin"))
    if cfg.model == "kgcn":
        return (("spmm", "spmm"), ("dense", "matmul"), ("act", "nonlin"))
    if cfg.model == "kgin":
        return (("act", "nonlin"),)
    if cfg.model == "rgcn":
        return tuple((f"basis{b}", "matmul") for b in range(cfg.n_bases)) \
            + (("self", "matmul"), ("act", "nonlin"))
    raise ValueError(cfg.model)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: KGNNConfig) -> dict:
    ks = iter(jax.random.split(key, 64))
    d = cfg.dim
    p = {
        "entity": normal_init(next(ks), (cfg.n_nodes, d), 0.1),
        "relation": normal_init(next(ks), (cfg.n_relations, d), 0.1),
    }
    dims = (d,) + cfg.dims
    if cfg.model == "kgat":
        # relation-space projection for attention (TransR style). The paper
        # uses a dense d×d W_r per relation; gathering it per edge is an
        # (E,d,d) tensor — infeasible at industry scale. We keep the
        # relation-specific d×d structure via basis decomposition
        # W_r = Σ_b a_rb V_b (R-GCN trick): project once per basis (B·N·d),
        # mix per edge with (E,B) coefficients. See DESIGN.md §3.
        p["att_basis"] = normal_init(next(ks), (cfg.n_bases, d, d), 0.1)
        p["att_coef"] = normal_init(next(ks), (cfg.n_relations, cfg.n_bases), 0.1)
        p["w1"] = [glorot(next(ks), (a, b)) for a, b in zip(dims[:-1], dims[1:])]
        p["w2"] = [glorot(next(ks), (a, b)) for a, b in zip(dims[:-1], dims[1:])]
    elif cfg.model == "kgcn":
        p["w"] = [glorot(next(ks), (a, b)) for a, b in zip(dims[:-1], dims[1:])]
        p["b"] = [jnp.zeros((b,)) for b in dims[1:]]
    elif cfg.model == "kgin":
        p["intent"] = normal_init(next(ks), (cfg.n_intents, cfg.n_relations), 0.1)
    elif cfg.model == "rgcn":
        p["basis"] = normal_init(next(ks), (cfg.n_bases, d, d), 0.1)
        p["coef"] = normal_init(next(ks), (cfg.n_relations, cfg.n_bases), 0.1)
        p["w_self"] = [glorot(next(ks), (d, d)) for _ in range(cfg.n_layers)]
    else:
        raise ValueError(cfg.model)
    return p


# ---------------------------------------------------------------------------
# propagation (paper Eq. 1/2) — layer math written once, against a view
# ---------------------------------------------------------------------------


def kgat_bi_interaction(p, layer: int, e: jax.Array, e_n: jax.Array, *,
                        keys: dict | None = None,
                        policies: dict | None = None) -> jax.Array:
    """Bi-interaction aggregator: LeakyReLU(W1(e+eN)) + LeakyReLU(W2(e⊙eN)).

    The single source of the (e, e_n) -> layer-output math for every
    KGAT path. With ``keys``/``policies`` omitted the ``w1``/``w2``/
    ``act1``/``act2`` sites resolve from the ambient ActContext; the
    explicitly-partitioned paths (shard_map bodies, where ambient
    resolution can't reach) pass per-site dicts instead — the DP
    bit-exactness contract rests on both paths running THIS code.
    """
    k = keys or {}
    po = policies or {}
    add = act_matmul(e + e_n, p["w1"][layer], scope="w1",
                     key=k.get("w1"), policy=po.get("w1"))
    mul = act_matmul(e * e_n, p["w2"][layer], scope="w2",
                     key=k.get("w2"), policy=po.get("w2"))
    add = act_nonlin(add, fn="leaky_relu", scope="act1",
                     key=k.get("act1"), policy=po.get("act1"))
    mul = act_nonlin(mul, fn="leaky_relu", scope="act2",
                     key=k.get("act2"), policy=po.get("act2"))
    return add + mul


def _basis_project(e: jax.Array, basis: jax.Array) -> jax.Array:
    """(rows, d) x (B, d, k) -> (B, rows, k): Σ_j e[:, j]·V_b[j, :] as an
    unrolled chain of elementwise multiply-adds. Each output element is
    then one fixed sequence of float ops, whatever the row count; XLA's
    CPU dot emitters pick a summation order by shape, so a matmul here
    would break the sharded paths' bit-exact reps (the 2D mesh contract,
    DESIGN.md §12.3)."""
    acc = e[None, :, 0:1] * basis[:, 0:1, :]
    for j in range(1, e.shape[1]):
        acc = acc + e[None, :, j:j + 1] * basis[:, j:j + 1, :]
    return acc


def _basis_mix(coef: jax.Array, proj: jax.Array) -> jax.Array:
    """Σ_b coef[:, b]·proj[b] per edge: (E, B) x (B, E, d) -> (E, d),
    elementwise for the same reason as ``_basis_project``."""
    acc = coef[:, 0:1] * proj[0]
    for b in range(1, proj.shape[0]):
        acc = acc + coef[:, b:b + 1] * proj[b]
    return acc


def _kgat_attention(p, e: jax.Array, view) -> jax.Array:
    """π(h,r,t) = (W_r e_t)ᵀ tanh(W_r e_h + e_r), softmaxed over dst.

    W_r = Σ_b a_rb V_b: basis-projected node tables (B, N, d) are computed
    once, then mixed per edge — O(B·N·d² + E·B·d) instead of O(E·d²).
    On a shard view the projection runs on local rows, the source side
    reads the all-gathered + halo-shrunk table, pad edges are masked out
    of the softmax normalization.
    """
    with jax.named_scope("attention"):
        proj = _basis_project(e, p["att_basis"])          # (B, rows, d)
        proj_t = view.table(proj, axis=1)                 # (B, H, d)
        coef = p["att_coef"][view.rel]                    # (E, B)
        eh = _basis_mix(coef, proj_t[:, view.src])
        et = _basis_mix(coef, proj[:, view.dst])
        logits = jnp.sum(et * jnp.tanh(eh + p["relation"][view.rel]),
                         axis=-1)
        logits = view.mask_logits(logits)
        return view.mask_weights(
            segment_softmax(logits, view.dst, view.num_rows))


def _kgat_layer(p, layer: int, e: jax.Array, view, att: jax.Array, *,
                keys: dict | None = None,
                policies: dict | None = None) -> jax.Array:
    """One KGAT layer; keys/policies omitted resolve from the ambient
    ActContext at the scoped sites (``.../spmm``, ``.../w1`` ...)."""
    k = keys or {}
    po = policies or {}
    e_n = act_spmm(view.table(e), view.src, view.dst, att,
                   num_nodes=view.num_rows, scope="spmm",
                   layout=view.layout, key=k.get("spmm"),
                   policy=po.get("spmm"))
    return kgat_bi_interaction(p, layer, view.self_rows(e), e_n, keys=keys,
                               policies=policies)


def _kgcn_layer(p, layer: int, e: jax.Array, view, ew: jax.Array, *,
                keys: dict | None = None,
                policies: dict | None = None) -> jax.Array:
    """KGNN-LS graph convolution: σ((Â E)Θ + b) with relation-scored Â."""
    k = keys or {}
    po = policies or {}
    h = act_spmm(view.table(e), view.src, view.dst, ew,
                 num_nodes=view.num_rows, scope="spmm", layout=view.layout,
                 key=k.get("spmm"), policy=po.get("spmm"))
    j = act_matmul(h + view.self_rows(e), p["w"][layer], scope="dense",
                   key=k.get("dense"), policy=po.get("dense"))
    j = j + p["b"][layer]
    return act_nonlin(j, scope="act",
                      fn="tanh" if layer == len(p["w"]) - 1 else "sigmoid",
                      key=k.get("act"), policy=po.get("act"))


def _kgin_layer(p, e: jax.Array, r_emb: jax.Array, view, *,
                keys: dict | None = None,
                policies: dict | None = None) -> jax.Array:
    """Relational path aggregation: e_h' = Σ_{(r,t)} e_r ⊙ e_t (KGIN eq. 8)."""
    k = keys or {}
    po = policies or {}
    # modulate by relation embedding per edge: gather-then-scale is O(E d);
    # act_spmm with per-edge weights handles the scalar part, the vector
    # modulation composes as two spmm passes over (e ⊙ e_r)-projected feats.
    # The aggregation is the "spmm" site, as act_spmm is for the others.
    with jax.named_scope("spmm"):
        gathered = view.table(e)[view.src] * r_emb[view.rel]  # (E, d)
        gathered = view.mask_messages(gathered)
        deg = jax.ops.segment_sum(view.edge_ones(e.dtype), view.dst,
                                  num_segments=view.num_rows)
        agg = jax.ops.segment_sum(gathered, view.dst,
                                  num_segments=view.num_rows)
        agg = agg / jnp.maximum(deg, 1.0)[:, None]
    return act_nonlin(agg, fn="leaky_relu", scope="act",
                      key=k.get("act"), policy=po.get("act"))


def _rgcn_layer(p, layer: int, e: jax.Array, view, *,
                keys: dict | None = None,
                policies: dict | None = None) -> jax.Array:
    """Basis-decomposed R-GCN: W_r = Σ_b a_rb V_b (basis-first projection)."""
    k = keys or {}
    po = policies or {}
    # project once per basis: (rows, B, d)
    proj = jnp.stack([
        act_matmul(e, p["basis"][b], scope=f"basis{b}",
                   key=k.get(f"basis{b}"), policy=po.get(f"basis{b}"))
        for b in range(p["basis"].shape[0])
    ], axis=1)
    coef_e = p["coef"][view.rel]                     # (E, B)
    msgs = jnp.einsum("eb,ebd->ed", coef_e, view.table(proj)[view.src])
    msgs = view.mask_messages(msgs)
    deg = jax.ops.segment_sum(view.edge_ones(e.dtype), view.dst,
                              num_segments=view.num_rows)
    agg = jax.ops.segment_sum(msgs, view.dst, num_segments=view.num_rows)
    agg = agg / jnp.maximum(deg, 1.0)[:, None]
    self_t = act_matmul(view.self_rows(e), p["w_self"][layer], scope="self",
                        key=k.get("self"), policy=po.get("self"))
    return act_nonlin(agg + self_t, fn="leaky_relu", scope="act",
                      key=k.get("act"), policy=po.get("act"))


def _edge_weights(params: dict, e0: jax.Array, view, cfg: KGNNConfig):
    """Per-edge weighting data, computed ONCE from the layer-0 embeddings.

    kgat: attention probabilities (E,); kgcn: relation-scored adjacency
    (E,); kgin: the intent-weighted relation table (R, d) its per-layer
    modulation reads; rgcn: nothing (coefficients are per-layer params).

    On a ``SampledGraphView`` the edge set differs per hop, so the
    edge-space weightings (kgat/kgcn) come back as a per-hop tuple —
    each hop's weights still computed from the SAME layer-0 embeddings
    (every hop frontier is a prefix of the outermost gathered table, so
    block-local indices are valid positions into ``e0``), preserving
    the once-from-layer-0 semantics the full-graph and DP paths pin.
    ``view.layer_weights`` slices the tuple back out per layer.
    """
    if isinstance(view, SampledGraphView) and cfg.model in ("kgat", "kgcn"):
        return tuple(_edge_weights(params, e0, b, cfg) for b in view.blocks)
    if cfg.model == "kgat":
        return _kgat_attention(params, e0, view)
    if cfg.model == "kgcn":
        # relation scores are user-agnostic at graph level (KGNN-LS's
        # label-smoothed global graph); per-edge weight = softmax over
        # dst of r·mean
        logits = jnp.sum(params["relation"][view.rel]
                         * view.table(e0)[view.src], axis=-1)
        logits = view.mask_logits(logits)
        return view.mask_weights(
            segment_softmax(logits, view.dst, view.num_rows))
    if cfg.model == "kgin":
        # intent-weighted relation embeddings
        alpha = jax.nn.softmax(params["intent"], axis=-1)   # (P, R)
        r_int = alpha @ params["relation"]                  # (P, d)
        return params["relation"] + jnp.mean(r_int, 0)      # broadcast
    if cfg.model == "rgcn":
        return None
    raise ValueError(cfg.model)


def propagate_view(params: dict, view, cfg: KGNNConfig, *, ctx=None,
                   site_keys=None, site_policies=None) -> list:
    """L layers of message passing against a view; returns per-layer outs.

    Exactly one of two resolution modes:
      * ``ctx`` (an entered ``ActContext``) — ambient per-site resolution
        under ``layer<l>`` scopes, the single-device path;
      * ``site_keys``/``site_policies`` — per-layer ``{site: ...}`` dicts
        pre-derived OUTSIDE a ``shard_map`` body (closed-over tracers are
        off-limits inside one), the data-parallel path.
    """
    e = view.local_rows(params["entity"])
    outs = [view.seed_rows(e)]
    weights = _edge_weights(params, e, view, cfg)
    for l in range(cfg.n_layers):
        lview = view.layer_view(l)
        w = view.layer_weights(weights, l)
        keys = site_keys[l] if site_keys is not None else None
        pols = site_policies[l] if site_policies is not None else None
        scope = ctx.scope(f"layer{l}") if ctx is not None \
            else contextlib.nullcontext()
        with scope:
            if cfg.model == "kgat":
                e = _kgat_layer(params, l, e, lview, w,
                                keys=keys, policies=pols)
            elif cfg.model == "kgcn":
                e = _kgcn_layer(params, l, e, lview, w,
                                keys=keys, policies=pols)
            elif cfg.model == "kgin":
                e = _kgin_layer(params, e, w, lview,
                                keys=keys, policies=pols)
            elif cfg.model == "rgcn":
                e = _rgcn_layer(params, l, e, lview,
                                keys=keys, policies=pols)
            else:
                raise ValueError(cfg.model)
        outs.append(view.seed_rows(e))
    return outs


def readout(outs: list, cfg: KGNNConfig) -> jax.Array:
    if cfg.readout == "concat":
        return jnp.concatenate(outs, axis=-1)
    if cfg.readout == "sum":
        return sum(outs)
    return outs[-1]


def propagate(params: dict, g: CKG, cfg: KGNNConfig, *,
              policy: ACTPolicy | PolicySchedule | None = None,
              key: jax.Array | None = None):
    """Run L layers of message passing; returns final node representations.

    ``policy``/``key`` omitted resolve from the ambient ``ActContext``
    (explicit kwargs build a local one; no context at all means FP32).
    Under an active stochastic policy a key (or a context root key) is
    REQUIRED — there is no silent constant-key fallback, which would
    replay identical rounding noise every step and void the
    unbiasedness-in-expectation argument (Proposition 1).
    """
    ctx = model_context(policy, key)
    ctx.check_key(f"propagate({cfg.model})")
    view = FullGraphView(g)
    with ctx, ctx.scope(cfg.model):
        outs = propagate_view(params, view, cfg, ctx=ctx)
    return readout(outs, cfg)


# ---------------------------------------------------------------------------
# recommendation head (BPR)
# ---------------------------------------------------------------------------


def propagate_spmd(params: dict, g: CKG, cfg: KGNNConfig, *, mesh, axes,
                   policy: ACTPolicy | PolicySchedule | None = None,
                   key: jax.Array | None = None):
    """Explicitly-partitioned KGAT propagation (shard_map).

    Layout (same scheme as gnn.gcn_forward_spmd, §Perf hillclimb #3):
    entity rows sharded over ``axes``; edges partitioned BY DESTINATION
    shard (``g.src`` global ids, ``g.dst`` LOCAL row ids). Attention is
    computed ONCE from the layer-0 embeddings — the same semantics as
    single-device ``propagate`` and the generic DP step (it used to be
    recomputed per layer from the evolving embeddings, a silent semantic
    fork; tests/test_distributed.py pins the aligned behavior against
    ``propagate``). Per layer: one tiled all-gather of the (N, d) entity
    matrix; the weighted scatter runs shard-local. The layer transforms
    stay GSPMD (row-sharded matmuls).

    Keys/policies resolve per scoped site like ``propagate``; the SPMM key
    is derived OUTSIDE shard_map (``ctx.scope_path`` + ``key_for``) and
    rides in replicated — closed-over tracers are off-limits inside a
    shard_map body. The in-body ``act_spmm`` still records its residual
    under the same site name: what each device buffers is Quant(e_full),
    the all-gathered table, which is exactly the recorded shape.

    For end-to-end data-parallel *training* prefer
    ``repro.training.data_parallel.make_dp_step`` (halo-shrunk gathers,
    compressed gradient all-reduce, any registered KG arch).
    """
    from repro.sharding.compat import P, shard_map

    assert cfg.model == "kgat", "spmd propagate implemented for KGAT"
    ctx = model_context(policy, key)
    ctx.check_key("propagate_spmd(kgat)")
    e = params["entity"]

    def att_local(e_loc, basis, src_g, dst_l, rel, coef, r_emb):
        # e_loc (N/D, d) local entity rows; src_g GLOBAL ids, dst_l LOCAL
        # dst rows (edges pre-partitioned by destination shard)
        proj_loc = _basis_project(e_loc, basis)             # (B, N/D, d)
        proj_full = jax.lax.all_gather(proj_loc, axes, axis=1, tiled=True)
        eh = _basis_mix(coef[rel], proj_full[:, src_g])
        et = _basis_mix(coef[rel], proj_loc[:, dst_l])
        logits = jnp.sum(et * jnp.tanh(eh + r_emb[rel]), axis=-1)
        return segment_softmax(logits, dst_l, e_loc.shape[0])

    att_fn = shard_map(
        att_local, mesh=mesh,
        in_specs=(P(axes, None), P(None, None, None), P(axes), P(axes),
                  P(axes), P(None, None), P(None, None)),
        out_specs=P(axes))
    att = att_fn(e, params["att_basis"], g.src, g.dst, g.rel,
                 params["att_coef"], params["relation"])

    def layer_local(e_loc, src_g, dst_l, att_e, att_key, *, spmm_policy):
        e_full = jax.lax.all_gather(e_loc, axes, axis=0, tiled=True)
        return act_spmm(e_full, src_g, dst_l, att_e,
                        num_nodes=e_loc.shape[0], key=att_key,
                        policy=spmm_policy)

    outs = [e]
    with ctx, ctx.scope(cfg.model):
        for l in range(cfg.n_layers):
            with ctx.scope(f"layer{l}"):
                site = ctx.scope_path("spmm")  # not registered: the op
                pol = ctx.policy_for("spmm", site)  # inside claims the name
                k_spmm = ctx.key_for(site)
                spmd_layer = shard_map(
                    functools.partial(layer_local, spmm_policy=pol or FP32),
                    mesh=mesh,
                    in_specs=(P(axes, None), P(axes), P(axes), P(axes), P()),
                    out_specs=P(axes, None))
                e_n = spmd_layer(e, g.src, g.dst, att,
                                 k_spmm if k_spmm is not None
                                 else jax.random.PRNGKey(0))
                e = kgat_bi_interaction(params, l, e, e_n)
            outs.append(e)
    return jnp.concatenate(outs, axis=-1) if cfg.readout == "concat" \
        else sum(outs)


def score_pairs(reps: jax.Array, users: jax.Array, items: jax.Array,
                n_users: int) -> jax.Array:
    """ŷ_uv = e_uᵀ e_v; item node ids are offset by n_users in the CKG."""
    return jnp.sum(reps[users] * reps[items + n_users], axis=-1)


def bpr_loss(params: dict, g: CKG, batch: dict, cfg: KGNNConfig, *,
             policy: ACTPolicy | PolicySchedule | None = None,
             key: jax.Array | None = None):
    """BPR pairwise ranking loss + L2 (the KGAT/KGIN objective)."""
    reps = propagate(params, g, cfg, policy=policy, key=key)
    with jax.named_scope("bpr"):
        pos = score_pairs(reps, batch["user"], batch["pos"], cfg.n_users)
        neg = score_pairs(reps, batch["user"], batch["neg"], cfg.n_users)
        loss = -jnp.mean(jax.nn.log_sigmoid(pos - neg))
        reg = sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(params))
        return loss + cfg.l2 * reg


def sampled_reps(params: dict, view: "SampledGraphView", cfg: KGNNConfig, *,
                 policy: ACTPolicy | PolicySchedule | None = None,
                 key: jax.Array | None = None) -> jax.Array:
    """Seed-row readout representations from a sampled minibatch.

    ``params["entity"]`` must already be the gathered outermost row
    table (``view.n_input_rows`` rows) — the tier cache's job. Scopes
    are the SAME ``<model>/layer<l>/<site>`` paths as ``propagate``, so
    an ACT schedule and its scope-hashed SR keys apply unchanged to
    sampled training.
    """
    ctx = model_context(policy, key)
    ctx.check_key(f"sampled_reps({cfg.model})")
    with ctx, ctx.scope(cfg.model):
        outs = propagate_view(params, view, cfg, ctx=ctx)
    return readout(outs, cfg)


def sampled_bpr_loss(params: dict, view: "SampledGraphView", cfg: KGNNConfig,
                     *, policy: ACTPolicy | PolicySchedule | None = None,
                     key: jax.Array | None = None):
    """BPR over a seed layout of ``[users | pos items | neg items]``.

    The sampler packs the three BPR roles as the seed set in fixed
    thirds (``B = n_seeds // 3``), so scoring is position-based — no
    global-id indexing into a full rep table exists on this path.
    L2 regularization covers the touched parameters only (the gathered
    entity rows + dense params), the sampled-approximate counterpart of
    the full-table term; see DESIGN.md §11 exactness ledger.
    """
    reps = sampled_reps(params, view, cfg, policy=policy, key=key)
    b = view.n_seeds // 3
    with jax.named_scope("bpr"):
        pos = jnp.sum(reps[:b] * reps[b:2 * b], axis=-1)
        neg = jnp.sum(reps[:b] * reps[2 * b:3 * b], axis=-1)
        loss = -jnp.mean(jax.nn.log_sigmoid(pos - neg))
        reg = sum(jnp.sum(x ** 2) for x in jax.tree_util.tree_leaves(params))
        return loss + cfg.l2 * reg


def kg_shard_loss(params: dict, view, batch: dict, cfg: KGNNConfig, *,
                  site_keys=None, site_policies=None):
    """One shard's slice of the global BPR objective (plus full L2 reg).

    Runs the SAME ``propagate_view`` layer math as single-device
    ``propagate`` — there is no hand-inlined DP forward. Returns
    ``(local_batch_mean_bpr + reg, local_batch_mean_bpr)``; with the
    batch sharded evenly and params replicated, the shard-mean of the
    first element is exactly the global objective.
    """
    outs = propagate_view(params, view, cfg, site_keys=site_keys,
                          site_policies=site_policies)
    reps = view.unshard(readout(outs, cfg))
    pos = score_pairs(reps, batch["user"], batch["pos"], cfg.n_users)
    neg = score_pairs(reps, batch["user"], batch["neg"], cfg.n_users)
    loss_loc = -jnp.mean(jax.nn.log_sigmoid(pos - neg))
    # view.param_l2 == plain leaf sum-of-squares everywhere except the
    # 2D mesh view, which psums row-sharded tables to the same scalar.
    reg = view.param_l2(params)
    return loss_loc + cfg.l2 * reg, loss_loc


def kg_shard_reps(params: dict, view, cfg: KGNNConfig, *,
                  site_keys=None, site_policies=None) -> jax.Array:
    """This shard's rows of the readout representations (parity tests)."""
    return readout(propagate_view(params, view, cfg, site_keys=site_keys,
                                  site_policies=site_policies), cfg)


# Memory accounting (paper Table 5) is derived from the residual trace —
# run the loss under a recording ActContext (or use
# ``repro.core.traced_activation_report``) instead of the old
# hand-maintained ``activation_shapes`` table, which had already drifted
# from the real ctx chain (it priced a phantom spmm residual for KGIN,
# whose aggregation never routes through act_spmm).
