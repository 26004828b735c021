"""Fault-tolerant training loop.

Production behaviors implemented (and unit-tested in tests/test_trainer.py):
  * checkpoint/restart: periodic async checkpoints; on step failure the
    loop restores the last good checkpoint and replays. SR randomness is
    keyed by global step (``step_key``), so replayed steps reproduce the
    same stochastic rounding — restarts are bit-deterministic.
  * bounded retries: ``max_failures`` consecutive failures aborts.
  * straggler mitigation: the host data queue has a fetch timeout; a
    straggling shard is skipped (batch re-sampled) rather than stalling
    the step, and slow-step telemetry (EMA) is logged.
  * elastic scaling hook: on restore, a new mesh/template may be supplied
    (fewer/more hosts) — the checkpoint reshards via device_put.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Iterator

import jax

from repro.obs import get_registry, log as obs_log, span

from .checkpoint import CheckpointManager

__all__ = ["TrainerConfig", "Trainer", "PrefetchIterator"]


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int
    ckpt_dir: str
    ckpt_every: int = 100
    keep: int = 3
    log_every: int = 50
    max_failures: int = 3
    fetch_timeout_s: float = 30.0


class PrefetchIterator:
    """Background-thread prefetch with timeout — the straggler guard.

    A data shard that exceeds ``timeout_s`` is skipped (the producer keeps
    running; the consumer just takes the next ready batch).

    ``close()`` genuinely stops the producer: puts use a bounded-timeout
    loop that re-checks the done flag, so a producer blocked on a full
    queue (the common steady state — the consumer is the slow side)
    observes shutdown instead of outliving the trainer. A plain
    ``Queue.put`` would block forever once the consumer stops taking.
    """

    _PUT_POLL_S = 0.05

    def __init__(self, it: Iterator, depth: int = 2,
                 timeout_s: float = 30.0):
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._timeout = timeout_s
        self._done = False

        def worker():
            source = iter(it)
            while True:
                with span("train/data/sample"):
                    item = next(source, StopIteration)
                if item is StopIteration or not self._put(item):
                    break
            self._put(StopIteration)

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Bounded-timeout put; False once the iterator is closed."""
        while not self._done:
            try:
                self._q.put(item, timeout=self._PUT_POLL_S)
                return True
            except queue.Full:
                continue
        return False

    def next(self):
        item = self._q.get(timeout=self._timeout)
        if item is StopIteration:
            raise StopIteration
        return item

    def close(self, join_timeout_s: float = 5.0):
        """Stop the producer thread and drain pending items."""
        self._done = True
        while True:  # unblock a producer waiting on a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=join_timeout_s)


class Trainer:
    """Drives ``train_step(state, batch, step) -> (state, metrics)``.

    ``state`` is any pytree (params + optimizer state). The step function
    must be jitted by the caller (the trainer is model-agnostic).
    """

    def __init__(self, train_step: Callable, state, data_iter: Iterator,
                 cfg: TrainerConfig, *, eval_fn: Callable | None = None,
                 log_fn: Callable | None = None,
                 ckpt_meta: dict | None = None, step_writer=None,
                 items_per_step: int | None = None,
                 item_unit: str = "edges"):
        self.train_step = train_step
        self.state = state
        self.cfg = cfg
        self.data = PrefetchIterator(data_iter, timeout_s=cfg.fetch_timeout_s)
        # ckpt_meta (arch id + schedule spec, see step_metadata) rides in
        # every manifest and is enforced on restore — a checkpoint from a
        # different arch/schedule fails loudly instead of resuming wrong
        self.ckpt = CheckpointManager(cfg.ckpt_dir, keep=cfg.keep,
                                      meta=ckpt_meta)
        self.eval_fn = eval_fn
        # progress lines go to stderr through the leveled obs log (stdout
        # stays machine-parseable); callers may still inject their own
        self.log = log_fn if log_fn is not None else obs_log
        self.step = 0
        self.history: list[dict] = []
        self._failures = 0
        self.total_failures = 0   # every failed step, rolled back or not
        self.step_ms: list[float] = []   # device-synced wall ms per step
        self._step_ema: float | None = None
        # failure-injection hook for tests: fn(step) -> bool (raise?)
        self.failure_injector: Callable | None = None
        # telemetry: per-step wall time (enqueue to device done, the same
        # quantity the straggler EMA watches), loss at log points,
        # throughput when the caller supplies the per-step work size
        self.step_writer = step_writer
        self.items_per_step = items_per_step
        self.item_unit = item_unit
        reg = get_registry()
        self._m_steps = reg.counter("train/steps")
        self._m_step_ms = reg.histogram("train/step_ms")
        self._m_loss = reg.gauge("train/loss")
        self._m_tput = (reg.gauge(f"train/{item_unit}_per_sec")
                        if items_per_step else None)

    def restore_if_available(self):
        step, state = self.ckpt.restore(self.state)
        if step is not None:
            self.step, self.state = step, state
            self.log(f"[trainer] restored checkpoint at step {step}")
        return self

    def run(self):
        # one-line topology breadcrumb: SPMD steps (shard_map over a
        # simulated or real mesh) look identical from here, so make the
        # device layout part of the log contract for post-mortems
        self.log(f"[trainer] {jax.device_count()} device(s), "
                 f"backend={jax.default_backend()}, "
                 f"start step {self.step}/{self.cfg.total_steps}")
        try:
            with span("train"):
                return self._run()
        finally:
            self.data.close()  # don't leak the prefetch producer thread

    def _run(self):
        cfg = self.cfg
        while self.step < cfg.total_steps:
            try:
                with span("train/step", step=self.step):
                    with span("train/step/data"):
                        batch = self.data.next()
                    if self.failure_injector is not None and \
                            self.failure_injector(self.step):
                        raise RuntimeError(
                            f"injected failure at step {self.step}")
                    t0 = time.perf_counter()
                    with span("train/step/update"):
                        self.state, metrics = self.train_step(
                            self.state, batch, self.step)
                        # the window ends when the device is done, not
                        # when the step is enqueued
                        jax.block_until_ready((self.state, metrics))
                    dt = time.perf_counter() - t0
                self._step_ema = dt if self._step_ema is None else \
                    0.9 * self._step_ema + 0.1 * dt
                self._m_steps.inc()
                self._m_step_ms.observe(dt * 1e3)
                self.step_ms.append(dt * 1e3)
                if self._m_tput is not None and dt > 0:
                    self._m_tput.set(self.items_per_step / dt)
                # straggler telemetry: flag steps 3x slower than EMA
                if dt > 3.0 * self._step_ema and self.step > 10:
                    self.log(f"[trainer] straggler step {self.step}: "
                             f"{dt:.3f}s vs ema {self._step_ema:.3f}s")
                self.step += 1
                self._failures = 0
                with span("train/log"):
                    self._log_step(dt, metrics)
                if self.step % cfg.ckpt_every == 0:
                    self.ckpt.save(self.step, self.state)
            except StopIteration:
                break
            except Exception as e:  # noqa: BLE001 — fault tolerance boundary
                self._failures += 1
                self.total_failures += 1
                self.log(f"[trainer] step {self.step} failed "
                         f"({self._failures}/{cfg.max_failures}): {e}")
                if self._failures >= cfg.max_failures:
                    raise
                step, state = self.ckpt.restore(self.state)
                if step is not None:
                    self.step, self.state = step, state
                    self.log(f"[trainer] rolled back to step {step}")
        self.ckpt.save(self.step, self.state)
        self.ckpt.wait()
        return self.state

    def _log_step(self, dt: float, metrics: dict) -> None:
        """The log point (the metrics' host conversion, ``history``, the
        progress line, the loss gauge) and the step writer's record."""
        record = None
        if self.step_writer is not None:
            record = {"step": self.step, "wall_ms": round(dt * 1e3, 4)}
        if self.step % self.cfg.log_every == 0:
            m = {k: float(v) for k, v in metrics.items()}
            self.history.append({"step": self.step, **m})
            self.log(f"[trainer] step {self.step}: {m}")
            if "loss" in m:
                self._m_loss.set(m["loss"])
            if record is not None:
                record.update(m)
        if record is not None:
            self.step_writer.write(record)
