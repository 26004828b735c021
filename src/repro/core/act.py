"""Activation-compressed ops (the paper's core mechanism, §3.3).

Each op computes an EXACT full-precision forward; what differs from vanilla
autodiff is the *residual* it saves for the backward pass:

  vanilla:  save x (fp32)                  -> O(N*d*4) bytes
  TinyKG:   save Quant(x) (b-bit packed)   -> O(N*d*b/8) bytes  (+2 fp32/row)

The backward pass dequantizes and computes full-precision gradients, which
stay unbiased because the quantizer is unbiased (Proposition 1).

Ops mirror the paper's operator list (Linear/MM, ReLU, SPMM, nonlinearities,
norms) plus a generic ``act_remat`` wrapper (beyond-paper: checkpointing that
recomputes the forward from the *compressed* input, GACT-style), which is how
we ACT-ify whole transformer blocks with one call.

Linear ops only need their input saved to form the *weight* gradient
(∇Θ = x̂ᵀ ∇y); the data gradient uses only the weights. Purely index-based
linear ops (embedding lookup, fixed-adjacency SPMM) need no activation at
all — their residuals are indices, which autodiff already keeps compactly.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .context import current_context
from .policy import ACTPolicy, FP32
from .quant import QTensor, dequantize, quantize

__all__ = [
    "act_matmul",
    "act_dense",
    "act_relu",
    "act_nonlin",
    "act_rmsnorm",
    "act_spmm",
    "act_remat",
]


# ---------------------------------------------------------------------------
# context resolution (DESIGN.md §6)
#
# Every public op accepts explicit ``key=`` / ``policy=`` kwargs (the
# pre-context API, still first in precedence) and an optional ``scope=``
# site name. Whatever is omitted resolves from the ambient ActContext; with
# no context either, the policy defaults to FP32. The resolved site is
# recorded on the context (residual shape/bits) for traced memory
# accounting.
#
# Each op's body runs under ``jax.named_scope(<site>)`` (the site name it
# qualifies, without the ``#k`` suffix), and the residual's quantize and
# dequantize under ``act_quant`` / ``act_dequant``: the compiler keeps
# these in every instruction's ``op_name``, which is how device time is
# put down to op sites (DESIGN.md §13). The name stack feeds no scope
# path, policy or key.
# ---------------------------------------------------------------------------


@functools.cache
def _dummy_key() -> jax.Array:
    # placeholder riding the op signature when no randomness is consumed
    # (inactive policy or nearest rounding); built concretely, because
    # the cache outlives the trace that may first ask for it
    with jax.ensure_compile_time_eval():
        return jax.random.PRNGKey(0)


def _resolve_site(op_kind: str, scope: str | None, key,
                  policy: ACTPolicy | None, *, need_key: bool = True):
    """(scope, policy, key, ctx) for one op call; see block comment above."""
    ctx = current_context()
    name = None
    if ctx is not None:
        name = ctx.qualify(scope or op_kind)
        if policy is None:
            policy = ctx.policy_for(op_kind, name)
        if key is None:
            key = ctx.key_for(name)
    if policy is None:
        policy = FP32
    if key is None:
        if need_key and policy.requires_key:
            raise ValueError(
                f"act op {name or scope or op_kind!r}: stochastic rounding "
                "under an active policy needs a PRNG key — pass key=, or "
                "run inside act_context(..., root_key=...). (A fixed "
                "default key would replay identical rounding noise.)")
        key = _dummy_key()
    return name, policy, key, ctx


def _record(ctx, name, op_kind, shape, policy: ACTPolicy) -> None:
    # bits=None prices the uncompressed fp32 residual — what vanilla
    # autodiff buffers when the policy is inactive/disabled
    if ctx is not None and name is not None:
        ctx.record(name, op_kind, shape,
                   policy.bits if policy.active else None)


def _maybe_quantize(x: jax.Array, key: jax.Array, policy: ACTPolicy):
    """QTensor under an active policy, raw tensor otherwise (FP32 baseline)."""
    if not policy.active:
        return x
    with jax.named_scope("act_quant"):
        if policy.kernel == "pallas":
            from repro.kernels import ops as kops

            return kops.quantize(x, key, bits=policy.bits,
                                 stochastic=policy.stochastic)
        return quantize(x, key, bits=policy.bits,
                        stochastic=policy.stochastic)


def _maybe_dequantize(q) -> jax.Array:
    if not isinstance(q, QTensor):
        return q
    with jax.named_scope("act_dequant"):
        return dequantize(q)


# ---------------------------------------------------------------------------
# matmul / dense
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _act_matmul(policy: ACTPolicy, x, w, key):
    return jnp.einsum("...k,kn->...n", x, w)


def _act_matmul_fwd(policy, x, w, key):
    out = jnp.einsum("...k,kn->...n", x, w)
    return out, (_maybe_quantize(x, key, policy), w)


def _act_matmul_bwd(policy, res, g):
    qx, w = res
    xhat = _maybe_dequantize(qx)
    dx = jnp.einsum("...n,kn->...k", g, w)
    if policy.active and policy.kernel == "pallas":
        from repro.kernels import ops as kops

        dw = kops.dequant_matmul(qx, g)  # fused dequant + Ĥᵀ∇J GEMM
    else:
        dw = jnp.einsum("...k,...n->kn", xhat, g)
    return dx, dw, None


_act_matmul.defvjp(_act_matmul_fwd, _act_matmul_bwd)


def act_matmul(x, w, *, key=None, policy: ACTPolicy | None = None,
               scope: str | None = None):
    """``x @ w`` with b-bit residual storage of ``x``.

    ``key``/``policy`` omitted resolve from the ambient ``ActContext`` at
    the site named ``scope`` (default ``"matmul"``); see DESIGN.md §6.
    """
    name, policy, key, ctx = _resolve_site("matmul", scope, key, policy)
    _record(ctx, name, "matmul", x.shape, policy)
    with jax.named_scope(scope or "matmul"):
        if not policy.enabled:
            return jnp.einsum("...k,kn->...n", x, w)
        return _act_matmul(policy, x, w, key)


def act_dense(x, w, b, *, key=None, policy: ACTPolicy | None = None,
              scope: str | None = None):
    """Affine layer; bias grad needs no activation so it rides for free."""
    out = act_matmul(x, w, key=key, policy=policy, scope=scope)
    if b is not None:
        out = out + b
    return out


# ---------------------------------------------------------------------------
# elementwise nonlinearities
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _act_relu(x):
    return jnp.maximum(x, 0)


def _act_relu_fwd(x):
    mask = x > 0
    return jnp.where(mask, x, 0), mask  # bool mask: 1 bit/elt in principle


def _act_relu_bwd(mask, g):
    return (jnp.where(mask, g, 0),)


_act_relu.defvjp(_act_relu_fwd, _act_relu_bwd)


def act_relu(x, *, scope: str | None = None):
    """ReLU with a 1-bit exact mask residual (paper §4.1.4) — lossless.

    Policy-independent (the mask is exact at any bit-width); under an
    ambient context the mask still shows up in the residual trace.
    """
    ctx = current_context()
    if ctx is not None:
        ctx.record(ctx.qualify(scope or "relu"), "relu", x.shape, 1,
                   exact_mask=True)
    with jax.named_scope(scope or "relu"):
        return _act_relu(x)


def _d_silu(x):
    s = jax.nn.sigmoid(x)
    return s * (1 + x * (1 - s))


def _d_gelu(x):
    # tanh-approx gelu derivative
    c = jnp.sqrt(2 / jnp.pi)
    t = jnp.tanh(c * (x + 0.044715 * x**3))
    dt = (1 - t**2) * c * (1 + 3 * 0.044715 * x**2)
    return 0.5 * (1 + t) + 0.5 * x * dt


def _gelu(x):
    c = jnp.sqrt(2 / jnp.pi)
    return 0.5 * x * (1 + jnp.tanh(c * (x + 0.044715 * x**3)))


_NONLIN: dict[str, tuple[Callable, Callable]] = {
    "silu": (jax.nn.silu, _d_silu),
    "gelu": (_gelu, _d_gelu),
    "tanh": (jnp.tanh, lambda x: 1 - jnp.tanh(x) ** 2),
    "sigmoid": (jax.nn.sigmoid,
                lambda x: jax.nn.sigmoid(x) * (1 - jax.nn.sigmoid(x))),
    "leaky_relu": (lambda x: jnp.where(x > 0, x, 0.01 * x),
                   lambda x: jnp.where(x > 0, 1.0, 0.01)),
}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _act_nonlin(name: str, policy: ACTPolicy, x, key):
    return _NONLIN[name][0](x)


def _act_nonlin_fwd(name, policy, x, key):
    return _NONLIN[name][0](x), _maybe_quantize(x, key, policy)


def _act_nonlin_bwd(name, policy, qx, g):
    xhat = _maybe_dequantize(qx)
    return g * _NONLIN[name][1](xhat), None


_act_nonlin.defvjp(_act_nonlin_fwd, _act_nonlin_bwd)


def act_nonlin(x, *, fn: str, key=None, policy: ACTPolicy | None = None,
               scope: str | None = None):
    """Elementwise nonlinearity saving a quantized copy of its input."""
    name, policy, key, ctx = _resolve_site("nonlin", scope or fn, key, policy)
    _record(ctx, name, "nonlin", x.shape, policy)
    with jax.named_scope(scope or fn):
        if not policy.enabled:
            return _NONLIN[fn][0](x)
        return _act_nonlin(fn, policy, x, key)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _act_rmsnorm(policy: ACTPolicy, x, gamma, key, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r * gamma


def _act_rmsnorm_fwd(policy, x, gamma, key, eps):
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * r * gamma, (_maybe_quantize(x, key, policy), gamma, eps)


def _act_rmsnorm_bwd(policy, res, g):
    qx, gamma, eps = res
    xhat = _maybe_dequantize(qx).astype(jnp.float32)
    gf = g.astype(jnp.float32)
    d = xhat.shape[-1]
    r = jax.lax.rsqrt(jnp.mean(xhat * xhat, axis=-1, keepdims=True) + eps)
    gg = gf * gamma.astype(jnp.float32)
    dot = jnp.sum(gg * xhat, axis=-1, keepdims=True)
    dx = r * gg - (r**3 / d) * dot * xhat
    dgamma = jnp.sum(gf * xhat * r, axis=tuple(range(g.ndim - 1)))
    return dx.astype(g.dtype), dgamma.astype(gamma.dtype), None, None


_act_rmsnorm.defvjp(_act_rmsnorm_fwd, _act_rmsnorm_bwd)


def act_rmsnorm(x, gamma, *, key=None, policy: ACTPolicy | None = None,
                scope: str | None = None, eps: float = 1e-6):
    """RMSNorm storing its input quantized; rstd recomputed from x̂ in bwd."""
    name, policy, key, ctx = _resolve_site("rmsnorm", scope, key, policy)
    _record(ctx, name, "rmsnorm", x.shape, policy)
    with jax.named_scope(scope or "rmsnorm"):
        if not policy.enabled:
            r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            return x * r * gamma
        return _act_rmsnorm(policy, x, gamma, key, eps)


# ---------------------------------------------------------------------------
# SPMM (KG message passing) — the paper's headline op (Eq. 2)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _act_spmm(policy: ACTPolicy, num_nodes: int, x, src, dst, ew, key):
    msgs = x[src] * ew[:, None]
    return jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)


def _act_spmm_fwd(policy, num_nodes, x, src, dst, ew, key):
    msgs = x[src] * ew[:, None]
    out = jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)
    # x is needed only for ∇ew (edge weights, e.g. KGAT attention); indices
    # alone suffice for ∇x. Save x quantized.
    return out, (_maybe_quantize(x, key, policy), src, dst, ew)


def _act_spmm_bwd(policy, num_nodes, res, g):
    qx, src, dst, ew = res
    xhat = _maybe_dequantize(qx)
    g_at_dst = g[dst]  # (E, d)
    # scatter to x's OWN row count — x may be a gathered (global) table
    # while num_nodes is the (local) output segment count (shard_map path)
    dx = jax.ops.segment_sum(g_at_dst * ew[:, None], src,
                             num_segments=xhat.shape[-2])
    dew = jnp.sum(xhat[src] * g_at_dst, axis=-1)
    return dx, None, None, dew, None


_act_spmm.defvjp(_act_spmm_fwd, _act_spmm_bwd)


# -- fused Pallas path: blocked-CSR layout, no (E, d) message tensor --------
#
# The layout pytree is flattened into explicit array args (custom_vjp
# forbids closed-over tracers and integer leaves take None cotangents,
# same as src/dst above); its treedef rides as a static nondiff arg.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _act_spmm_pallas(policy: ACTPolicy, treedef, x, ew, key, *leaves):
    from repro.kernels import ops as kops

    layout = jax.tree_util.tree_unflatten(treedef, leaves)
    return kops.spmm(x, ew, layout)


def _act_spmm_pallas_fwd(policy, treedef, x, ew, key, *leaves):
    from repro.kernels import ops as kops

    layout = jax.tree_util.tree_unflatten(treedef, leaves)
    out = kops.spmm(x, ew, layout)
    return out, (_maybe_quantize(x, key, policy), ew, leaves)


def _act_spmm_pallas_bwd(policy, treedef, res, g):
    from repro.kernels import ops as kops

    qx, ew, leaves = res
    layout = jax.tree_util.tree_unflatten(treedef, leaves)
    # ∇x: scatter-transpose — the same fused kernel on the src-sorted
    # direction of the layout (all-gatherᵀ analogue, no (E, d) tensor)
    dx = kops.spmm(g, ew, layout, transpose=True).astype(g.dtype)
    # ∇ew: fused dequant-SDDMM reading the packed residual directly
    dew = kops.spmm_grad_ew(qx, g, layout).astype(ew.dtype)
    return (dx, dew, None) + (None,) * len(leaves)


_act_spmm_pallas.defvjp(_act_spmm_pallas_fwd, _act_spmm_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _spmm_linear_pallas(treedef, x, *leaves):
    from repro.kernels import ops as kops

    return kops.spmm(x, None, jax.tree_util.tree_unflatten(treedef, leaves))


def _spmm_linear_pallas_fwd(treedef, x, *leaves):
    from repro.kernels import ops as kops

    out = kops.spmm(x, None, jax.tree_util.tree_unflatten(treedef, leaves))
    return out, leaves


def _spmm_linear_pallas_bwd(treedef, leaves, g):
    from repro.kernels import ops as kops

    layout = jax.tree_util.tree_unflatten(treedef, leaves)
    dx = kops.spmm(g, None, layout, transpose=True)
    return (dx,) + (None,) * len(leaves)


_spmm_linear_pallas.defvjp(_spmm_linear_pallas_fwd, _spmm_linear_pallas_bwd)


def _pallas_layout_ok(layout, x, src, num_nodes: int) -> bool:
    """Fused-kernel eligibility; anything else falls back to jnp. Only
    shapes and dtypes decide: oversized tables reach the HBM-DMA kernels
    through ``kernels.ops``."""
    if layout is None or x.ndim != 2:
        return False
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    m = layout.meta
    return (m.n_edges == src.shape[0] and m.n_dst == num_nodes
            and m.n_src == x.shape[0])


def act_spmm(x, src, dst, ew, *, num_nodes: int, key=None,
             policy: ACTPolicy | None = None, scope: str | None = None,
             layout=None):
    """Weighted sparse aggregation ``H[v] = Σ_{(u,r,v)} w_e · x[u]``.

    ``src``/``dst`` are int edge endpoints, ``ew`` per-edge weights. When
    ``ew`` is None (plain normalized adjacency, e.g. GCN/KGCN) the op is
    linear with index-only residuals — nothing to compress, handled exactly
    (and nothing is recorded in the residual trace).

    ``layout`` is an optional blocked-CSR ``repro.data.csr.SpmmLayout``
    for the same edge list. Under ``ACTPolicy(kernel="pallas")`` it routes
    forward, ∇x and ∇ew through the fused Pallas kernels (no ``(E, d)``
    message tensor in HBM). The automatic jnp fallback covers *shape*
    mismatches only — a missing layout, different edge/node counts, or
    an unsupported dtype. A layout built for a *different edge list of
    the same sizes* is indistinguishable at trace time and would
    silently aggregate along the wrong edges: the caller owns keeping
    ``layout`` in sync with ``src``/``dst`` (``CKG.layout`` rides inside
    the graph pytree precisely so they travel together).
    """
    name, policy, key, ctx = _resolve_site("spmm", scope, key, policy,
                                           need_key=ew is not None)
    fused = policy.kernel == "pallas" and \
        _pallas_layout_ok(layout, x, src, num_nodes)
    if ew is not None:
        _record(ctx, name, "spmm", x.shape, policy)
    with jax.named_scope(scope or "spmm"):
        if ew is None:
            if fused:
                leaves, treedef = jax.tree_util.tree_flatten(layout)
                return _spmm_linear_pallas(treedef, x, *leaves)
            msgs = x[src]
            return jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)
        if not policy.enabled:
            msgs = x[src] * ew[:, None]
            return jax.ops.segment_sum(msgs, dst, num_segments=num_nodes)
        if fused:
            leaves, treedef = jax.tree_util.tree_flatten(layout)
            return _act_spmm_pallas(policy, treedef, x, ew, key, *leaves)
        return _act_spmm(policy, num_nodes, x, src, dst, ew, key)


# ---------------------------------------------------------------------------
# Generic compressed-checkpoint wrapper (beyond-paper, GACT-style)
# ---------------------------------------------------------------------------


def act_remat(fn: Callable, policy: ACTPolicy | None = None, *,
              scope: str | None = None, repeat: int = 1):
    """Wrap ``fn(params, x, consts) -> y`` to save only Quant(x) backward.

    The backward pass dequantizes x̂ and *recomputes* ``fn`` under ``jax.vjp``
    — i.e. gradient checkpointing whose checkpoint is b-bit compressed. One
    wrapper ACT-ifies an entire block (attention + MLP) with O(N·d·b/8)
    residual memory instead of O(layers · activations).

    ``consts`` is a non-differentiated pytree (positions, masks, …) passed
    as an explicit argument — custom_vjp forbids closed-over tracers.
    Returns ``wrapped(params, x, key=None, consts=None)``; under an
    inactive policy it degrades to plain ``jax.checkpoint`` (the FP32
    baseline). Like every other act op, ``policy=None`` resolves from the
    ambient context at CALL time (site ``scope`` / ``"remat"``), so a
    block wrapped outside any context still honors the schedule it is
    later applied under; the quantized-input save is recorded per apply.
    ``repeat`` is for callers that apply the wrapped fn under
    ``jax.lax.scan`` (one trace, ``repeat`` runtime applications): the
    residual trace then carries one record per buffered instance.
    """

    explicit_policy = policy

    @functools.lru_cache(maxsize=None)
    def active_path(pol: ACTPolicy):
        # one custom_vjp instance per resolved policy (hashable dataclass)
        @jax.custom_vjp
        def wrapped(params, x, key, consts):
            return fn(params, x, consts)

        def fwd(params, x, key, consts):
            return fn(params, x, consts), (
                params, _maybe_quantize(x, key, pol), consts)

        def bwd(res, g):
            params, qx, consts = res
            xhat = _maybe_dequantize(qx)
            _, vjp = jax.vjp(lambda p, xx: fn(p, xx, consts), params, xhat)
            dparams, dx = vjp(g)
            return dparams, dx, None, None

        wrapped.defvjp(fwd, bwd)
        return wrapped

    baseline = None  # lazy jax.checkpoint, shared across applies

    def apply(params, x, key=None, consts=None):
        nonlocal baseline
        name, pol, key, ctx = _resolve_site("remat", scope, key,
                                            explicit_policy)
        _record(ctx, name, "remat", x.shape, pol)
        for i in range(1, repeat):  # scan buffers `repeat` instances
            _record(ctx, None if name is None else f"{name}[{i}]",
                    "remat", x.shape, pol)
        with jax.named_scope(scope or "remat"):
            if not pol.active:
                if baseline is None:
                    baseline = jax.checkpoint(
                        lambda params, x, consts: fn(params, x, consts))
                return baseline(params, x, consts)
            return active_path(pol)(params, x, key, consts)

    return apply
