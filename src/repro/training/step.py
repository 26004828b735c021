"""The model-step registry's step type: ONE step definition per arch.

TinyKG's claim is framework-level — *any* KGNN trains with compressed
activations — and scaling work (Data Tiering, Min et al. 2022) assumes
the training step is a reusable unit. ``ModelStep`` is that unit
(DESIGN.md §9): the launcher, the ``Trainer``, the data-parallel wrapper
(``repro.training.data_parallel.make_dp_step``), the examples and the
benchmarks all consume the same object instead of re-deriving a step per
model.

Protocol (structural — ``repro.models.registry`` builds concrete
instances from the existing layer functions):

  * ``init(key, data_spec=None) -> params`` — parameter pytree;
  * ``loss(params, batch, *, ctx=None) -> scalar`` — the training
    objective, with every ACT site resolved through the ordinary
    ``ActContext`` scopes. ``ctx`` is entered by the step (pass a fresh
    ``act_context(schedule, root, step=i)`` per trace); ``ctx=None``
    leaves ambient resolution to the caller (e.g. a recording context
    for ``traced_activation_report``);
  * ``dp_spec`` — what is edge-sharded over the data axis and how each
    parameter lays out over the model axis (``ShardSpec``; ``DPSpec``
    is its pre-2D alias), or ``None`` with ``dp_unsupported`` naming
    why mesh parallelism does not apply;
  * ``batches() -> iterator`` — the step's default data stream (the
    launcher's; examples/benchmarks bring their own sizes).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Iterator, Protocol, runtime_checkable

import jax

from repro.core import act_context

__all__ = ["ShardSpec", "DPSpec", "ROW_SHARDED", "REPLICATED", "ModelStep",
           "ModelStepProtocol", "make_train_step", "step_metadata"]

# Per-parameter placement kinds for ``ShardSpec.placement`` (DESIGN.md
# §12). REPLICATED is the default for any parameter not listed.
ROW_SHARDED = "rows"
REPLICATED = "replicated"


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """What a step shards vs replicates under mesh parallelism.

    The data axis: ``graph`` is the COO edge structure to dst-partition
    (``repro.data.csr.partition_edges``); the batch shards evenly over
    the mesh's data axis. ``sites`` lists the per-layer ACT sites
    ``(name, op_kind)`` whose policies/keys must be pre-resolved OUTSIDE
    the ``shard_map`` body, under ``<scope>/layer<l>/<site>`` scopes —
    the same paths the single-device step uses, so a sharded step
    replays the same rounding noise at the same sites.

    The model axis: ``placement`` declares, per top-level parameter
    name, how the parameter lays out over the mesh's model axis —
    ``(name, ROW_SHARDED)`` splits dim 0 into per-shard row blocks
    (embedding tables); anything not listed is REPLICATED. On a 1D
    ``data=N`` mesh the placement is inert and every parameter is
    replicated, which is exactly the pre-2D behavior.
    """

    graph: Any                     # CKG to dst-partition
    scope: str                     # root scope name (e.g. "kgat")
    sites: tuple                   # ((site_name, op_kind), ...) per layer
    n_layers: int
    # (params, view, batch, *, site_keys, site_policies)
    #   -> (local objective incl. reg, local batch loss)
    shard_loss: Callable = None
    # (params, view, *, site_keys, site_policies) -> local readout rows;
    # optional, used by the forward-parity tests
    shard_reps: Callable | None = None
    # ((top_level_param_name, ROW_SHARDED), ...); unlisted => replicated
    placement: tuple = ()

    def row_sharded(self) -> tuple:
        """Top-level param names row-sharded over the model axis."""
        return tuple(n for n, kind in self.placement if kind == ROW_SHARDED)

    def placement_str(self) -> str:
        """Stable string form for checkpoint metadata (``"entity=rows"``)."""
        return ",".join(f"{n}={kind}" for n, kind in self.placement)


# The pre-2D name: ShardSpec generalizes DPSpec (placement defaults to
# all-replicated), so every existing DPSpec(...) construction and
# isinstance check keeps working unchanged.
DPSpec = ShardSpec


@runtime_checkable
class ModelStepProtocol(Protocol):
    arch: str
    dp_spec: DPSpec | None

    def init(self, key, data_spec=None): ...

    def loss(self, params, batch, *, ctx=None): ...


@dataclasses.dataclass(frozen=True)
class ModelStep:
    """Concrete step record the registry builds (see module docstring).

    ``init``/``loss``/``batches`` are plain callables bound over the
    step's config and data, so the dataclass satisfies
    ``ModelStepProtocol`` by attribute access.
    """

    arch: str                      # registry id ("kgat", "fm", ...)
    family: str                    # kgnn | gnn | recsys | lm | moe_lm
    cfg: Any                       # model config dataclass
    init: Callable                 # init(key, data_spec=None) -> params
    loss: Callable                 # loss(params, batch, *, ctx=None)
    batches: Callable[[], Iterator]
    lr: float = 1e-3               # launcher default learning rate
    dp_spec: DPSpec | None = None
    dp_unsupported: str | None = None   # why dp_spec is None, for errors
    data: dict = dataclasses.field(default_factory=dict)  # bound data refs
    data_spec: dict = dataclasses.field(default_factory=dict)  # shapes/sizes

    def metadata(self) -> dict:
        """Checkpoint-facing identity (see ``step_metadata``)."""
        return {"arch": self.arch, "family": self.family,
                "model": getattr(self.cfg, "model", self.family)}


def step_metadata(step: ModelStep, schedule_spec: str | None = None, *,
                  mesh_spec=None, placement: str | None = None) -> dict:
    """Identity a checkpoint carries so restore can't silently mismatch.

    ``schedule_spec`` is the CLI-level policy string (``"int8"``,
    ``"first_layer_int8_rest_int2"``, ...): restoring a run under a
    different arch or schedule is almost always a mistake — the
    ``CheckpointManager`` refuses it instead of producing silently-wrong
    training.

    ``mesh_spec`` (a ``MeshSpec`` or its string form) and ``placement``
    (``ShardSpec.placement_str()``) record the mesh topology and
    per-table layout of sharded runs: a 2D checkpoint's row-sharded
    tables are padded to the mesh's block geometry, so restoring onto a
    different layout is a shape-silent corruption — ``check_meta``
    refuses it naming both topologies (``--reshard-from`` is the
    explicit migration path).
    """
    meta = step.metadata()
    if schedule_spec is not None:
        meta["schedule"] = str(schedule_spec)
    if mesh_spec is not None:
        meta["mesh"] = str(mesh_spec)
    if placement is not None:
        meta["placement"] = str(placement)
    return meta


def make_train_step(step: ModelStep, opt, *, schedule=None,
                    root_key: jax.Array | None = None):
    """Jitted single-device ``train_step(state, batch, i)`` for ``Trainer``.

    Each trace enters a fresh ``act_context(schedule, root_key, step=i)``
    so every ACT site resolves its per-site policy and scope-hashed,
    replay-exact stochastic-rounding key — identical wiring for every
    registered arch.
    """

    @jax.jit
    def train_step(state, batch, i):
        params, opt_state = state

        def loss_fn(p):
            ctx = act_context(schedule, root_key, step=i)
            return step.loss(p, batch, ctx=ctx)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        with jax.named_scope("optimizer"):
            params, opt_state = opt.update(grads, opt_state, params)
        return (params, opt_state), {"loss": loss}

    return train_step


def enter_or_null(ctx) -> contextlib.AbstractContextManager:
    """``with enter_or_null(ctx):`` — ambient entry when a context is
    given, no-op otherwise (the ``loss(..., ctx=None)`` contract)."""
    return ctx if ctx is not None else contextlib.nullcontext()
