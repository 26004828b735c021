"""Fault-tolerant checkpointing (no orbax available — built from scratch).

Layout per step:  <dir>/step_<N>/arrays.npz + manifest.json
  * atomic: written to ``step_<N>.tmp`` then os.rename'd — a crash mid-save
    never corrupts the latest good checkpoint
  * keep-last-k garbage collection
  * optional async save on a background thread (training continues while
    the previous step serializes)
  * restore places leaves onto the shardings of a caller-provided template
    (so a checkpoint written on one mesh restores onto another — the
    elastic re-mesh path; leaves are full logical arrays, resharding is a
    device_put)
"""

from __future__ import annotations

import json
import os
import shutil
import threading

import jax
import numpy as np

from repro.obs import span

__all__ = ["save_checkpoint", "restore_checkpoint", "latest_step",
           "check_meta", "CheckpointManager"]


def _step_dir(base: str, step: int) -> str:
    return os.path.join(base, f"step_{step:010d}")


def save_checkpoint(base: str, step: int, tree, *, keep: int = 3,
                    meta: dict | None = None) -> str:
    """Synchronous atomic save. Returns the checkpoint directory.

    ``meta`` is an arbitrary JSON-serializable identity dict (arch id,
    schedule spec, ... — see ``repro.training.step.step_metadata``)
    stored in the manifest; ``restore_checkpoint`` refuses checkpoints
    whose stored identity contradicts the expected one.
    """
    os.makedirs(base, exist_ok=True)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    final = _step_dir(base, step)
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    arrays = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    manifest = {
        "step": step,
        "n_leaves": len(leaves),
        "treedef": str(treedef),
        "shapes": [list(np.shape(x)) for x in leaves],
        "dtypes": [str(np.asarray(x).dtype) for x in leaves],
    }
    if meta is not None:
        manifest["meta"] = meta
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)  # atomic publish
    _gc(base, keep)
    return final


def _gc(base: str, keep: int) -> None:
    steps = sorted(_list_steps(base))
    for s in steps[:-keep] if keep > 0 else []:
        shutil.rmtree(_step_dir(base, s), ignore_errors=True)


def _list_steps(base: str) -> list[int]:
    if not os.path.isdir(base):
        return []
    out = []
    for name in os.listdir(base):
        if name.startswith("step_") and not name.endswith(".tmp"):
            if os.path.exists(os.path.join(base, name, "manifest.json")):
                out.append(int(name[5:]))
    return out


def latest_step(base: str) -> int | None:
    steps = _list_steps(base)
    return max(steps) if steps else None


def check_meta(stored: dict | None, expected: dict | None,
               where: str = "") -> None:
    """Refuse a checkpoint whose stored identity contradicts the run's.

    Only keys present in BOTH dicts are compared (legacy checkpoints
    without metadata restore as before; extra keys on either side are
    informational, not contractual).
    """
    if not stored or not expected:
        return
    bad = {k: (stored[k], expected[k]) for k in stored
           if k in expected and stored[k] != expected[k]}
    if bad:
        detail = ", ".join(f"{k}: checkpoint={s!r} run={e!r}"
                           for k, (s, e) in sorted(bad.items()))
        hint = ""
        if "mesh" in bad or "placement" in bad:
            # a topology mismatch has a sanctioned migration path; name it
            hint = (" (for a mesh/placement change, launch.train's "
                    "--reshard-from gathers the old layout onto the new "
                    "mesh instead of resuming in place)")
        raise ValueError(
            f"checkpoint{' at ' + where if where else ''} was written for "
            f"a different run ({detail}); refusing a silent mismatch — "
            "point --ckpt at a fresh directory or match the original "
            "arch/schedule" + hint)


def restore_checkpoint(base: str, template, *, step: int | None = None,
                       expect_meta: dict | None = None):
    """Restore onto ``template``'s structure/dtypes/shardings.

    Returns (step, tree) or (None, template) when no checkpoint exists.
    ``expect_meta`` (arch id, schedule spec, ...) is validated against
    the manifest's stored metadata via ``check_meta``.
    """
    if step is None:
        step = latest_step(base)
    if step is None:
        return None, template
    d = _step_dir(base, step)
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    check_meta(manifest.get("meta"), expect_meta, where=d)
    with np.load(os.path.join(d, "arrays.npz")) as z:
        leaves = [z[f"leaf_{i}"] for i in range(len(z.files))]
    t_leaves, treedef = jax.tree_util.tree_flatten(template)
    assert len(leaves) == len(t_leaves), (
        f"checkpoint has {len(leaves)} leaves, template {len(t_leaves)}")
    placed = []
    for arr, t in zip(leaves, t_leaves):
        arr = arr.astype(t.dtype)
        if hasattr(t, "sharding") and t.sharding is not None:
            placed.append(jax.device_put(arr, t.sharding))
        else:
            placed.append(jax.device_put(arr))
    return step, jax.tree_util.tree_unflatten(treedef, placed)


class CheckpointManager:
    """Async keep-k checkpointing with a single background writer thread.

    ``meta`` (e.g. ``step_metadata(step, schedule_spec)``) is stamped
    into every save and enforced on every restore, so a checkpoint
    written for one arch/schedule can't silently resume another.
    """

    def __init__(self, base: str, *, keep: int = 3,
                 asynchronous: bool = True, meta: dict | None = None):
        self.base = base
        self.keep = keep
        self.asynchronous = asynchronous
        self.meta = meta
        self._thread: threading.Thread | None = None

    def save(self, step: int, tree) -> None:
        """Spans (DESIGN.md §13): ``train/ckpt`` around the whole call,
        ``train/ckpt/fetch`` (device to host copy) and ``train/ckpt/wait``
        (join of the previous writer) inside it, ``train/ckpt/write`` on
        the writer's thread."""
        with span("train/ckpt"):
            # materialize on host BEFORE handing to the thread so training
            # can donate/overwrite device buffers immediately
            with span("train/ckpt/fetch"):
                host_tree = jax.tree_util.tree_map(np.asarray, tree)
            with span("train/ckpt/wait"):
                self.wait()
            if self.asynchronous:
                self._thread = threading.Thread(
                    target=self._write, args=(step, host_tree), daemon=True)
                self._thread.start()
            else:
                self._write(step, host_tree)

    def _write(self, step: int, host_tree) -> None:
        with span("train/ckpt/write"):
            save_checkpoint(self.base, step, host_tree, keep=self.keep,
                            meta=self.meta)

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def restore(self, template, *, step: int | None = None):
        self.wait()
        return restore_checkpoint(self.base, template, step=step,
                                  expect_meta=self.meta)
