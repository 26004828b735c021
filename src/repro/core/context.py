"""The ACT context: named scopes, per-site policies, traced residuals.

``ActContext`` is a *trace-time* object (plain Python state, never traced
itself) that gives every compressed op three things when the explicit
``key=`` / ``policy=`` kwargs are omitted:

  * a **named scope** — a ``/``-joined path like ``"kgat/layer2/spmm"``
    built from ``ctx.scope(...)`` blocks plus the op's site name. Each
    component is also a ``jax.named_scope``, so the compiled ops carry
    the site in their ``op_name`` metadata (device time per op site);
    the name stack is write-only here, so no path or key depends on it;
  * a **policy** — resolved from the context's ``PolicySchedule`` by
    ``(op_kind, scope, layer)``, first matching rule wins;
  * a **stochastic-rounding key** — ``fold_in(fold_in(root, crc32(scope)),
    step)``, stable when ops are added/removed (unlike the positional
    ``KeyChain`` counter) and replay-exact across restarts.

The context also **records every residual the ops save** (scope, op kind,
shape, bits, exact-mask flag) while the function is traced, so activation-
memory accounting (``repro.core.memory``) is derived from the real ctx
chain instead of hand-maintained shape tables.

Usage — ambient (the common path)::

    with act_context(schedule, root_key=root, step=step):
        loss = bpr_loss(params, g, batch, cfg)   # ops self-resolve

or explicit per-call (``key=`` / ``policy=`` kwargs always win, so
migration is incremental).

Lifecycle: scope-name dedup (``#k`` suffixes for repeated names) and the
residual record list live on the context, so create a **fresh context per
traced function**; reuse across traces accumulates both.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator, Sequence

import jax

from .policy import ACTPolicy, PolicySchedule, as_schedule
from .rng import scope_key

__all__ = ["SavedResidual", "ActContext", "act_context", "current_context",
           "model_context"]


@dataclasses.dataclass(frozen=True)
class SavedResidual:
    """One residual the backward pass will hold, as seen at trace time.

    bits is the *storage* width (None = uncompressed fp32 baseline);
    exact_mask marks lossless 1-bit bool masks (ReLU), which carry no
    per-row scale/zero overhead.
    """

    scope: str
    op_kind: str
    shape: tuple[int, ...]
    bits: int | None
    exact_mask: bool = False


# Ambient context stack. Plain module state: JAX traces a function on one
# thread, and contexts are entered/exited at trace time only.
_ACTIVE: list["ActContext"] = []


def current_context() -> "ActContext | None":
    return _ACTIVE[-1] if _ACTIVE else None


class ActContext:
    """See module docstring. ``schedule`` accepts a bare ``ACTPolicy``."""

    def __init__(self, schedule: PolicySchedule | ACTPolicy | None = None,
                 root_key: jax.Array | None = None, *,
                 step: jax.Array | int = 0):
        self.schedule = as_schedule(schedule) if schedule is not None else None
        self.root_key = root_key
        self.step = step
        self.records: list[SavedResidual] = []
        self._stack: list[str] = []
        self._seen: dict[str, int] = {}

    # -- ambient management -------------------------------------------------

    def __enter__(self) -> "ActContext":
        _ACTIVE.append(self)
        return self

    def __exit__(self, *exc) -> None:
        popped = _ACTIVE.pop()
        assert popped is self, "ActContext exited out of order"

    @contextlib.contextmanager
    def scope(self, name: str) -> Iterator["ActContext"]:
        """Push a scope path component for the ops traced inside; it is
        also a ``jax.named_scope``, so compiled ops carry it in their
        ``op_name`` (the name stack never feeds a scope path or key)."""
        self._stack.append(name)
        try:
            with jax.named_scope(name):
                yield self
        finally:
            self._stack.pop()

    # -- per-site resolution ------------------------------------------------

    def scope_path(self, name: str) -> str:
        """Full scope path for a site name WITHOUT registering it.

        For call sites that need a site's policy/key ahead of the op call
        (e.g. threading a key into a shard_map body) while letting the op
        itself claim the name via ``qualify``.
        """
        return "/".join(self._stack + [name]) if self._stack else name

    def qualify(self, name: str) -> str:
        """Full scope path for a site name; repeats get ``#k`` suffixes.

        The suffix keeps keys unique when one scope name is hit twice in a
        trace while leaving every *other* site's path (hence key) alone.
        """
        full = self.scope_path(name)
        n = self._seen.get(full, 0)
        self._seen[full] = n + 1
        return full if n == 0 else f"{full}#{n}"

    def policy_for(self, op_kind: str, scope: str) -> ACTPolicy | None:
        if self.schedule is None:
            return None
        return self.schedule.resolve(op_kind, scope)

    def key_for(self, scope: str) -> jax.Array | None:
        if self.root_key is None:
            return None
        return scope_key(self.root_key, scope, self.step)

    # -- trace records ------------------------------------------------------

    def record(self, scope: str, op_kind: str, shape: Sequence[int],
               bits: int | None, *, exact_mask: bool = False) -> None:
        self.records.append(SavedResidual(
            scope=scope, op_kind=op_kind, shape=tuple(shape), bits=bits,
            exact_mask=exact_mask))

    def report(self) -> dict:
        """Price the recorded residuals (``repro.core.memory``)."""
        from .memory import activation_bytes_report

        return activation_bytes_report(self.records)

    # -- entry-point guard --------------------------------------------------

    def check_key(self, who: str) -> None:
        """Fail fast when SR randomness is needed but no root key exists.

        Silently substituting a constant key would reuse identical rounding
        noise every step, breaking the unbiasedness-in-expectation argument
        (Proposition 1 averages over independent draws).
        """
        if self.root_key is None and self.schedule is not None \
                and self.schedule.requires_key:
            raise ValueError(
                f"{who}: the active stochastic-rounding policy needs a PRNG "
                "key — pass key=, or enter act_context(..., root_key=...). "
                "(A fixed default key would replay identical rounding noise "
                "every step.)")


def act_context(schedule: PolicySchedule | ACTPolicy | None = None,
                root_key: jax.Array | None = None, *,
                step: jax.Array | int = 0) -> ActContext:
    """A fresh ``ActContext`` to be entered as the ambient context::

        with act_context(schedule, root_key, step=step) as ctx:
            ...
    """
    return ActContext(schedule, root_key, step=step)


def model_context(policy=None, key: jax.Array | None = None, *,
                  default: ACTPolicy | None = None) -> ActContext:
    """Context resolution for model entry points (``propagate`` etc.).

    Precedence: explicit kwargs beat the ambient context beats ``default``
    (FP32 when unset). With no explicit override an active ambient context
    is reused as-is; otherwise a local context is built, inheriting
    whatever the explicit kwargs leave unspecified from the ambient one —
    including its residual record list, so a recording trace still sees
    ops called with explicit overrides. Entering the returned context is
    always safe (re-entering the ambient context nests).
    """
    amb = current_context()
    if amb is not None and policy is None and key is None:
        return amb
    if policy is not None:
        schedule = as_schedule(policy)
    elif amb is not None and amb.schedule is not None:
        schedule = amb.schedule
    else:
        from .policy import FP32

        schedule = as_schedule(default if default is not None else FP32)
    root = key if key is not None else (
        amb.root_key if amb is not None else None)
    step = amb.step if amb is not None else 0
    ctx = ActContext(schedule, root, step=step)
    if amb is not None:
        # Shared sinks: the outer trace keeps collecting records, and scope
        # dedup stays global — a second model call reusing the same scope
        # names must get #k-suffixed sites (distinct SR keys, distinct
        # report entries), not silent collisions.
        ctx.records = amb.records
        ctx._seen = amb._seen
    return ctx
