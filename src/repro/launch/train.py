"""Training launcher: ``--arch <id>`` + shape -> fault-tolerant train loop.

Every arch resolves through the model-step registry
(``repro.models.registry.build_step``, DESIGN.md §9) to ONE ``ModelStep``
— there is no per-family job wiring here anymore. ``train()`` is the
training path itself; ``main`` feeds it the CLI and ``chip_smoke.py``
feeds it the published widths.

  PYTHONPATH=src python -m repro.launch.train --arch kgat --steps 100
  PYTHONPATH=src python -m repro.launch.train --arch fm --steps 50 --bits 4
  PYTHONPATH=src python -m repro.launch.train --arch kgat \
      --schedule first_layer_int8_rest_int2
  PYTHONPATH=src python -m repro.launch.train --arch kgin --mesh data=8

``--schedule`` takes a ``PolicySchedule`` spec (preset name, uniform
bit-width, or ordered ``[kind:]glob=bits`` rules — see
``repro.core.policy.parse_schedule``); each train step then runs inside an
``act_context`` so every op site resolves its own policy and
stochastic-rounding key (scope-hashed, replay-exact). ``--bits`` remains
the uniform fast path.

``--mesh`` takes a ``MeshSpec`` layout (``sharding/mesh_spec.py`` —
the one parser shared with dryrun and ``make_dp_step``) and runs the
shard_map path (DESIGN.md §7, §12) for EVERY arch whose step registers
a ``ShardSpec`` — all KG archs (kgat, kgcn, kgin):

  * ``--mesh data=N`` — 1D data parallelism: edges dst-partitioned
    over N shards, params replicated, per-shard ACT-compressed
    propagation through the same ``propagate_view`` layer math as the
    single-device step, gradients all-reduced through the INT8
    compressed psum (``--allreduce fp32`` for the exact baseline).
  * ``--mesh data=N,model=M`` — the 2D data×model mesh: additionally
    row-shards the embedding tables the step's placement marks
    (entity) over M model shards, so each device holds 1/M of the
    dominant table; gradients reduce per-axis.

Archs without a ShardSpec (lm / recsys / gcn) fail fast with the
reason. The mesh is built from the process's devices: the chips on an
accelerator; under ``JAX_PLATFORMS=cpu`` the N×M host devices are
forced automatically — provided no jax call has initialized the backend
first.

Checkpoints carry the run identity (arch id + schedule spec + mesh
topology + table placement): restoring from a directory written by a
different arch, schedule or mesh layout is refused instead of silently
resuming the wrong run. ``--reshard-from <dir>`` is the explicit
migration hatch: it restores a checkpoint IGNORING its mesh layout
(arch/schedule still checked) and re-pads the row-sharded tables onto
the current layout.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import os
import tempfile
import time

import jax

from repro.configs import ARCHS, get
from repro.core.policy import schedule_from_cli, schedule_label
from repro.obs import (StepLogWriter, get_registry, get_tracer, log, span,
                       write_summary)
from repro.obs.sites import keep_compiled
from repro.training.optimizer import adam
from repro.training.step import make_train_step, step_metadata
from repro.training.trainer import Trainer, TrainerConfig


def _parse_mesh(spec: str):
    """``--mesh`` string -> validated ``MeshSpec`` (SystemExit on junk)."""
    from repro.sharding.mesh_spec import MeshSpec, MeshSpecError

    try:
        return MeshSpec.parse(spec).check_axes(("data", "model"),
                                               required=("data",))
    except MeshSpecError as e:
        raise SystemExit(f"--mesh: {e}")


def _force_host_devices(n: int) -> None:
    """On a CPU run (``JAX_PLATFORMS=cpu``), request ``n`` host devices —
    only effective before the first jax call initializes the backend
    (``make_device_mesh`` raises the honest error with the manual fix if
    it is too late). Anywhere else the mesh is built from the real
    devices."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        return
    cur = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in cur:
        os.environ["XLA_FLAGS"] = \
            (cur + f" --xla_force_host_platform_device_count={n}").strip()


def _dp_train_step(step, mesh_spec, allreduce, opt, root_key, schedule):
    """--mesh: the generic shard_map path (1D data / 2D data×model)."""
    from repro.training import data_parallel as dp

    mesh = mesh_spec.build()
    part = dp.partition_graph(step.dp_spec.graph, mesh, axis="data")
    train_step = dp.make_dp_step(
        step, part, mesh, opt, schedule=schedule, root_key=root_key,
        mesh_spec=mesh_spec, compress_grads=allreduce == "int8")
    tables = ""
    if "model" in mesh_spec.names:
        tables = (f", row-sharded [{step.dp_spec.placement_str()}] over "
                  f"model={mesh_spec.extent('model')}")
    log(f"[train] data-parallel {step.arch}: mesh {mesh_spec}, "
        f"allreduce={allreduce}, "
        f"edges/shard≤{part.e_cap}, halo/shard≤{part.h_cap}{tables}")
    return train_step, part, mesh


def _run_sampled(arch, args, schedule, schedule_spec) -> None:
    """--sample: minibatch KG training through the tiered row store."""
    from repro.data.minibatch import parse_fanouts
    from repro.models.registry import build_step
    from repro.training import tiering

    try:
        fanouts = parse_fanouts(args.sample)
    except ValueError as e:
        raise SystemExit(f"error: {e}")
    kwargs = {"n_layers": len(fanouts)} if arch.family == "kgnn" else {}
    step = build_step(arch, schedule=schedule, **kwargs)
    log(f"[train] sampled {args.arch} ({arch.family}) "
        f"fanouts={fanouts} hot_frac={args.hot_frac} "
        f"schedule={schedule_spec}")
    try:
        report, _, store = tiering.run_sampled_training(
            step, fanouts=fanouts, steps=args.steps,
            batch_size=args.batch, hot_frac=args.hot_frac,
            schedule=schedule, root_key=jax.random.PRNGKey(1),
            init_key=jax.random.PRNGKey(0), log_fn=log)
    except ValueError as e:
        raise SystemExit(f"error: {e}")
    print(f"[train] done; loss {report.losses[0]:.4f} -> "
          f"{report.losses[-1]:.4f}  hit-rate {report.hit_rate:.2%}  "
          f"rows/step {report.rows_transferred_per_step:.0f}  "
          f"hot-tier {report.store_device_bytes/2**20:.2f} MiB of "
          f"{report.table_bytes/2**20:.2f} MiB table")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--bits", type=int, default=2, help="0 = FP32 baseline")
    ap.add_argument("--schedule", default=None,
                    help="PolicySchedule spec (preset | intN/fp32 | "
                         "'[kind:]glob=bits,...'); overrides --bits")
    ap.add_argument("--kernel", default="jnp", choices=["jnp", "pallas"],
                    help="ACT backend: jnp reference or fused Pallas kernels")
    ap.add_argument("--mesh", default=None,
                    help="MeshSpec layout, e.g. data=8 (1D data-parallel) "
                         "or data=4,model=2 (2D mesh with row-sharded "
                         "tables) over this process's devices (any arch "
                         "with a registered ShardSpec — kgat, kgcn, kgin)")
    ap.add_argument("--allreduce", default="int8", choices=["int8", "fp32"],
                    help="gradient all-reduce wire format on the --mesh "
                         "path (int8 = compressed SR psum)")
    ap.add_argument("--sample", default=None,
                    help="fanout=F1,F2,...: neighbor-sampled minibatch "
                         "training with hot/cold embedding tiering (KG "
                         "archs; one fanout per layer, seed-adjacent "
                         "first), e.g. --sample fanout=15,10")
    ap.add_argument("--hot-frac", type=float, default=0.1,
                    help="--sample: fraction of entity rows kept device-"
                         "resident (frequency-ranked hot tier)")
    ap.add_argument("--batch", type=int, default=256,
                    help="--sample: BPR batch size per sampled step")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome-trace/Perfetto JSON of the run's "
                         "host spans (train/step/... nesting; on TPU also "
                         "brackets StepTraceAnnotation)")
    ap.add_argument("--metrics-out", default=None, metavar="DIR",
                    help="write steps.jsonl (per-step timeline) and the "
                         "schema-validated summary.json registry snapshot "
                         "under DIR")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--reshard-from", default=None, metavar="DIR",
                    help="restore this checkpoint dir IGNORING its mesh "
                         "layout (arch/schedule still checked) and re-pad "
                         "row-sharded tables onto the current --mesh — the "
                         "explicit 1D->2D migration hatch")
    args = ap.parse_args()
    if args.sample and args.mesh:
        from repro.training.data_parallel import check_no_sampled_dp
        try:
            check_no_sampled_dp(args.sample, mesh_spec=args.mesh)
        except NotImplementedError as e:
            raise SystemExit(f"error: {e}")
    mesh_spec = _parse_mesh(args.mesh) if args.mesh else None
    if mesh_spec is not None:
        # must precede every jax call: the device count locks at first init
        _force_host_devices(mesh_spec.size)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    arch = get(args.arch)
    schedule = schedule_from_cli(args.schedule, args.bits, kernel=args.kernel)
    schedule_spec = schedule_label(args.schedule, args.bits)

    from repro.models.registry import build_step

    if args.trace:
        get_tracer().enable()
    run = {"kind": "train", "arch": args.arch, "family": arch.family,
           "schedule": schedule_spec, "steps": args.steps,
           "mesh": str(mesh_spec) if mesh_spec is not None else None,
           "sample": args.sample}

    if args.sample:
        _run_sampled(arch, args, schedule, schedule_spec)
        _finish_telemetry(args, run)
        return
    step = build_step(arch, schedule=schedule)
    if mesh_spec is not None and step.dp_spec is None:
        raise SystemExit(
            f"--mesh: data parallelism is not implemented for --arch "
            f"{args.arch} ({arch.family}): {step.dp_unsupported}")
    run_ = train(step, steps=args.steps, schedule=schedule,
                 schedule_spec=schedule_spec, mesh_spec=mesh_spec,
                 allreduce=args.allreduce, ckpt=args.ckpt,
                 reshard_from=args.reshard_from,
                 metrics_out=args.metrics_out)
    _finish_telemetry(args, run)
    losses = [h["loss"] for h in run_.trainer.history]
    print(f"[train] done; loss {losses[0]:.4f} -> {losses[-1]:.4f}"
          if losses else "[train] done")


@dataclasses.dataclass
class TrainRun:
    """What one ``train`` call leaves behind for its caller."""

    trainer: Trainer          # history, step_ms, failures, final state
    compile_s: float | None   # AOT compile of the single-device step


def train(step, *, steps: int, schedule, schedule_spec: str,
          mesh_spec=None, allreduce: str = "int8", ckpt: str | None = None,
          reshard_from: str | None = None, metrics_out: str | None = None,
          log_every: int | None = None, max_failures: int = 3) -> TrainRun:
    """The launcher's training path for one registry ``ModelStep``: build
    the (single-device or mesh) train step, init or restore the state,
    and drive the fault-tolerant ``Trainer`` for ``steps`` steps.

    ``main`` runs it with the CLI's values; ``chip_smoke.py`` runs it at
    the published widths, with ``max_failures=1`` so a step failure
    fails the run instead of rolling back.
    """
    opt = adam(step.lr)
    root = jax.random.PRNGKey(1)
    part = None
    if mesh_spec is not None:
        train_step, part, _ = _dp_train_step(step, mesh_spec, allreduce,
                                             opt, root, schedule)
    else:
        train_step = make_train_step(step, opt, schedule=schedule,
                                     root_key=root)
    n_model = (mesh_spec.extent("model")
               if mesh_spec is not None and "model" in mesh_spec.names
               else None)
    placement = (step.dp_spec.placement_str() if n_model is not None
                 else None)
    with span("setup/init"):
        params = step.init(jax.random.PRNGKey(0))
    if reshard_from:
        from repro.training import data_parallel as dp
        from repro.training.checkpoint import restore_checkpoint

        # template + expected meta deliberately carry NO mesh/placement:
        # resharding reads the source layout-agnostically (row tables at
        # their real row count), then re-pads for the current layout.
        template = (params, opt.init(params))
        rstep, state = restore_checkpoint(reshard_from, template,
                                          expect_meta=step_metadata(
                                              step, schedule_spec))
        if rstep is None:
            raise SystemExit(f"--reshard-from: no checkpoint found under "
                             f"{reshard_from}")
        # the template has no shardings, so restore committed every leaf
        # to one device; gather to host (uncommitted) so the train step's
        # shard_map is free to lay the tree out on the new mesh
        import numpy as np
        state = jax.tree_util.tree_map(np.asarray, state)
        if n_model is not None:
            state = dp.pad_row_sharded(state, step.dp_spec, part, n_model)
        log(f"[train] resharded checkpoint step {rstep} from "
            f"{reshard_from} onto mesh "
            f"{mesh_spec if mesh_spec is not None else '1 device'}")
    else:
        if n_model is not None:
            from repro.training import data_parallel as dp

            params = dp.pad_row_sharded(params, step.dp_spec, part, n_model)
        state = (params, opt.init(params))

    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"[train] {step.arch} ({step.family}) {n/1e6:.2f}M params "
        f"schedule={schedule_spec}")
    data_iter = step.batches()
    first = next(data_iter)
    data_iter = itertools.chain([first], data_iter)
    compile_s = None
    if mesh_spec is None:
        # compile ahead of the loop, so the first timed step is a run
        with span("setup/compile"):
            t0 = time.perf_counter()
            train_step = train_step.lower(state, first, 0).compile()
            compile_s = time.perf_counter() - t0
        get_registry().gauge("setup/compile_s").set(compile_s)
        keep_compiled("train/step", train_step)
        mem = train_step.memory_analysis()
        log(f"[train] step compiled in {compile_s:.2f}s; temp "
            f"{getattr(mem, 'temp_size_in_bytes', 'n/a')} B, arguments "
            f"{getattr(mem, 'argument_size_in_bytes', 'n/a')} B")
    step_writer = None
    if metrics_out:
        step_writer = StepLogWriter(os.path.join(metrics_out, "steps.jsonl"))
        if mesh_spec is None:
            # Table-5 pricing of THIS run's loss trace: price the
            # residuals the compressed ops would save for the first
            # batch (eval_shape — no FLOPs), publish as act/* gauges,
            # and stamp the total onto every step line so steps.jsonl
            # doubles as the activation-bytes timeline. Mesh runs skip
            # it: per-shard residual shapes live inside the shard_map
            # body.
            from repro.core.memory import (publish_activation_report,
                                           traced_activation_report)

            act = traced_activation_report(step.loss, params, first,
                                           schedule=schedule, key=root)
            publish_activation_report(act)
            step_writer.extras["act_total_bytes"] = act["total_bytes"]
            log(f"[train] activation footprint "
                f"{act['total_bytes']/2**20:.2f} MiB "
                f"({act['compression_ratio']:.1f}x vs fp32)")
    n_edges = (int(step.dp_spec.graph.src.shape[0])
               if step.dp_spec is not None else None)
    cfg = TrainerConfig(
        total_steps=steps,
        ckpt_dir=ckpt or tempfile.mkdtemp(prefix="repro_ckpt_"),
        ckpt_every=max(steps // 4, 10),
        log_every=log_every or max(steps // 8, 5),
        max_failures=max_failures)
    trainer = Trainer(train_step, state, data_iter, cfg,
                      ckpt_meta=step_metadata(step, schedule_spec,
                                              mesh_spec=mesh_spec,
                                              placement=placement),
                      step_writer=step_writer, items_per_step=n_edges)
    with span("setup/restore"):
        trainer.restore_if_available()
    try:
        trainer.run()
    finally:
        if step_writer is not None:
            step_writer.close()
    return TrainRun(trainer=trainer, compile_s=compile_s)


def _finish_telemetry(args, run: dict) -> None:
    """End-of-run artifacts: Perfetto trace and/or summary snapshot."""
    if args.trace:
        path = get_tracer().save(args.trace, run=run)
        log(f"[train] trace written to {path}")
    if args.metrics_out:
        path = write_summary(args.metrics_out, run)
        log(f"[train] metrics summary written to {path}")


if __name__ == "__main__":
    main()
