"""KG-aware neighbor-sampled minibatches with blocked-CSR hop layouts.

The full-graph training path requires the whole entity table and edge
set on one device — the exact ceiling TinyKG's activation compression
was meant to lift for industry-scale graphs. This module removes it for
every registered KG arch at once (DESIGN.md §11):

  * ``build_kg_csr`` — one-time host CSR over incoming edges, carrying
    relation ids (the KG-aware extension of ``data/sampler.py``);
  * ``sample_kg_blocks`` — per-hop fanout sampling that emits
    ``models.kgnn.BlockView`` bipartite blocks with STATIC padded
    shapes, honoring the **seeds-prefix invariant**: each hop's
    destination frontier is the leading prefix of its source frontier,
    so block-local indices are simultaneously valid positions into the
    outermost gathered table (per-hop KGAT/KGCN edge weights stay
    once-from-layer-0) and seed rows are ``[:n_seeds]`` of every layer
    output — concat readout works unchanged;
  * per-hop **blocked-CSR layouts** (``data/csr.py`` with
    ``pad_static=True``) whose geometry depends only on the static
    block shape, so the fused Pallas SPMM and ACT compression run
    unchanged on sampled subgraphs without retracing;
  * ``MinibatchStream`` — a background-thread pipeline (bounded queue,
    clean shutdown, in the style of ``trainer.PrefetchIterator``) that
    pairs BPR batches with freshly sampled blocks so host-side sampling
    overlaps device compute.

Sampling semantics: per destination node, ``fanout`` incoming edges are
drawn **with replacement** when the in-degree exceeds the fanout, and
taken exactly (without replacement, remainder masked) otherwise — so a
fanout at least the max in-degree reproduces the full neighborhood
exactly, which is what the gradient-parity tests pin. All our KG
aggregations normalize per destination (edge softmax or degree mean),
so masked uniform sampling keeps the neighbor-mean estimator unbiased;
softmax attention over a sampled subset is the standard
sampled-softmax approximation (see the DESIGN.md §11 exactness ledger).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

from repro.models.kgnn import BlockView, SampledGraphView

__all__ = ["KGAdjacency", "build_kg_csr", "sample_kg_blocks",
           "SampledItem", "sampled_items", "MinibatchStream",
           "parse_fanouts"]


@dataclasses.dataclass(frozen=True)
class KGAdjacency:
    """CSR over incoming edges: for each dst node its (src, rel) pairs."""

    indptr: np.ndarray    # (n_nodes + 1,) int64
    src: np.ndarray       # (E,) int64 source node per slot, dst-sorted
    rel: np.ndarray       # (E,) int64 relation id per slot
    n_nodes: int

    @property
    def max_in_degree(self) -> int:
        return int(np.max(self.indptr[1:] - self.indptr[:-1], initial=0))


def build_kg_csr(src, dst, rel, n_nodes: int) -> KGAdjacency:
    """Host-side CSR (incoming edges, relation ids along for the ride)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    rel = np.asarray(rel, np.int64)
    order = np.argsort(dst, kind="stable")
    counts = np.bincount(dst, minlength=n_nodes)
    indptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return KGAdjacency(indptr=indptr, src=src[order], rel=rel[order],
                      n_nodes=n_nodes)


def parse_fanouts(spec: str) -> tuple[int, ...]:
    """``"fanout=15,10"`` or ``"15,10"`` -> ``(15, 10)``."""
    body = spec.split("=", 1)[1] if "=" in spec else spec
    try:
        fanouts = tuple(int(x) for x in body.split(",") if x)
        if not fanouts or any(f < 1 for f in fanouts):
            raise ValueError
    except ValueError:
        raise ValueError(
            f"--sample expects fanout=F1,F2,... (one positive fanout per "
            f"layer, seed-adjacent hop first), got {spec!r}")
    return fanouts


def _one_hop(adj: KGAdjacency, frontier: np.ndarray, fanout: int,
             rng: np.random.Generator):
    """Sample one hop. Returns (nbr, rel, mask) each (n_dst, fanout)."""
    n_dst = len(frontier)
    deg = adj.indptr[frontier + 1] - adj.indptr[frontier]
    ar = np.arange(fanout)[None, :]
    # always draw, for stream determinism independent of degree layout
    drawn = rng.integers(0, np.maximum(deg, 1)[:, None], (n_dst, fanout))
    exact = deg[:, None] <= fanout
    offs = np.where(exact, np.minimum(ar, np.maximum(deg - 1, 0)[:, None]),
                    drawn)
    mask = np.where(exact, ar < deg[:, None], True)
    if len(adj.src) == 0:   # an edgeless graph: every slot is a pad
        nbr = np.repeat(frontier[:, None], fanout, axis=1)
        return nbr, np.zeros(nbr.shape, adj.rel.dtype), mask
    e_ix = np.minimum(adj.indptr[frontier][:, None] + offs,
                      len(adj.src) - 1)
    nbr = adj.src[e_ix]
    rel = adj.rel[e_ix]
    # masked slots become weight-0 self-edges: their endpoint MUST be a
    # member of the next frontier, and the dst's own id always is
    nbr = np.where(mask, nbr, frontier[:, None])
    rel = np.where(mask, rel, 0)
    return nbr, rel, mask


def _extend_frontier(frontier: np.ndarray, nbr: np.ndarray,
                     mask: np.ndarray, fanout: int) -> np.ndarray:
    """Next frontier ``[frontier | new unique neighbors | pad]`` with a
    static length ``len(frontier) * (fanout + 1)``; order-preserving
    dedup keeps the seeds-prefix invariant, pads cycle frontier ids."""
    cand = nbr.reshape(-1)[mask.reshape(-1)]
    cand = cand[~np.isin(cand, frontier)]
    _, first = np.unique(cand, return_index=True)
    new = cand[np.sort(first)]
    n_src = len(frontier) * (fanout + 1)
    pad = n_src - len(frontier) - len(new)
    return np.concatenate([frontier, new, np.resize(frontier, pad)]) \
        if pad else np.concatenate([frontier, new])


def sample_kg_blocks(adj: KGAdjacency, seeds: np.ndarray,
                     fanouts: tuple[int, ...], *,
                     rng: np.random.Generator, build_layouts: bool = False,
                     block_e: int = 256, block_rows: int = 256):
    """Sample ``len(fanouts)`` hops outward from ``seeds``.

    Returns ``(view, input_nodes, requests)``: a ``SampledGraphView``
    whose blocks are in EXECUTION order (outermost hop first — what
    layer 0 consumes), the outermost frontier's global node ids (the
    rows the tier cache must resolve), and the row-access stream WITH
    multiplicity (seeds + every real edge draw — what LFU frequency
    ranking and hit-rate accounting are measured over; the padded
    frontier would drown the signal in cycled duplicates on small
    graphs). ``fanouts`` are listed seed-outward:
    ``fanouts[0]`` is the hop adjacent to the seeds, consumed by the
    LAST layer. With ``build_layouts`` each block carries a
    static-geometry blocked-CSR ``SpmmLayout`` for the fused Pallas
    SPMM (``csr.build_spmm_layout(pad_static=True)``).
    """
    import jax.numpy as jnp

    from repro.data.csr import build_spmm_layout

    seeds = np.asarray(seeds, np.int64)
    if seeds.ndim != 1 or not len(seeds):
        raise ValueError(f"seeds must be a non-empty 1-D id array, "
                         f"got shape {seeds.shape}")
    if seeds.size and (seeds.min() < 0 or seeds.max() >= adj.n_nodes):
        raise ValueError(
            f"seed ids outside [0, {adj.n_nodes}): "
            f"[{seeds.min()}, {seeds.max()}]")
    blocks = []
    requests = [seeds]  # true row-access stream: seeds + real edge draws
    frontier = seeds
    for fanout in fanouts:
        n_dst = len(frontier)
        nbr, rel, mask = _one_hop(adj, frontier, fanout, rng)
        requests.append(nbr.reshape(-1)[mask.reshape(-1)])
        nxt = _extend_frontier(frontier, nbr, mask, fanout)
        # first occurrence position of every id present in nxt
        uq, first_pos = np.unique(nxt, return_index=True)
        e_src = first_pos[np.searchsorted(uq, nbr.reshape(-1))]
        e_dst = np.repeat(np.arange(n_dst, dtype=np.int64), fanout)
        layout = build_spmm_layout(
            e_src, e_dst, n_dst=n_dst, n_src=len(nxt),
            block_e=block_e, block_rows=block_rows, pad_static=True) \
            if build_layouts else None
        blocks.append(BlockView(
            src=jnp.asarray(e_src, jnp.int32),
            dst=jnp.asarray(e_dst, jnp.int32),
            rel=jnp.asarray(rel.reshape(-1), jnp.int32),
            mask=jnp.asarray(mask.reshape(-1), jnp.float32),
            layout=layout, n_src=len(nxt), n_dst=n_dst))
        frontier = nxt
    blocks.reverse()  # outermost hop first = execution order for layer 0
    return (SampledGraphView(blocks=tuple(blocks), n_seeds=len(seeds)),
            frontier, np.concatenate(requests))


# ---------------------------------------------------------------------------
# streaming loader
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SampledItem:
    """One prepared minibatch: blocks + the rows the tier cache must
    resolve. Seeds are packed ``[user nodes | pos item nodes | neg item
    nodes]`` (each a third), matching ``kgnn.sampled_bpr_loss``."""

    view: SampledGraphView
    input_nodes: np.ndarray    # (n_input_rows,) global entity ids
    requests: np.ndarray       # row-access stream with multiplicity
    batch: dict                # the raw BPR batch (user/pos/neg)
    index: int                 # stream position, for logging/replay


def sampled_items(ds, fanouts: tuple[int, ...], *, batch_size: int,
                  seed: int = 0, build_layouts: bool = False,
                  block_e: int = 256, block_rows: int = 256) -> Iterator:
    """Infinite deterministic stream of ``SampledItem``s for a
    ``KGDataset`` — BPR batch sampling and block sampling share one
    seeded generator, so a stream is replay-exact given its seed."""
    from repro.data.synthetic import bpr_batches

    g = ds.graph
    adj = build_kg_csr(np.asarray(g.src), np.asarray(g.dst),
                       np.asarray(g.rel), g.n_nodes)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xB10C]))
    for i, batch in enumerate(bpr_batches(ds, batch_size, seed=seed)):
        seeds = np.concatenate([
            batch["user"].astype(np.int64),
            ds.n_users + batch["pos"].astype(np.int64),
            ds.n_users + batch["neg"].astype(np.int64)])
        view, input_nodes, requests = sample_kg_blocks(
            adj, seeds, fanouts, rng=rng, build_layouts=build_layouts,
            block_e=block_e, block_rows=block_rows)
        yield SampledItem(view=view, input_nodes=input_nodes,
                          requests=requests, batch=batch, index=i)


class MinibatchStream:
    """Background-thread minibatch pipeline with bounded queue and clean
    shutdown — ``PrefetchIterator`` machinery applied to the sampler, so
    CSR traversal / dedup / layout construction overlap device compute.
    """

    def __init__(self, ds, fanouts: tuple[int, ...], *, batch_size: int,
                 seed: int = 0, build_layouts: bool = False,
                 block_e: int = 256, block_rows: int = 256,
                 depth: int = 2, timeout_s: float = 60.0):
        from repro.training.trainer import PrefetchIterator

        self.fanouts = tuple(fanouts)
        self._pf = PrefetchIterator(
            sampled_items(ds, self.fanouts, batch_size=batch_size,
                          seed=seed, build_layouts=build_layouts,
                          block_e=block_e, block_rows=block_rows),
            depth=depth, timeout_s=timeout_s)

    def next(self) -> SampledItem:
        return self._pf.next()

    def close(self) -> None:
        self._pf.close()

    def __enter__(self) -> "MinibatchStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
