"""Compressed-op gradient tests: exact where exact, bounded-noise where SR."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    act_matmul,
    act_nonlin,
    act_relu,
    act_remat,
    act_rmsnorm,
    act_spmm,
)
from repro.core.policy import FP32, INT8, ACTPolicy

KEY = jax.random.PRNGKey(0)


def test_matmul_dx_exact_any_bits():
    """∇x uses only weights — exact regardless of quantization."""
    x = jax.random.normal(KEY, (16, 32))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (32, 8))
    for bits in (None, 8, 2, 1):
        pol = ACTPolicy(bits=bits)
        gx = jax.grad(lambda x_: (act_matmul(
            x_, w, key=KEY, policy=pol) ** 2).sum())(x)
        exact = jax.grad(lambda x_: ((x_ @ w) ** 2).sum())(x)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(exact),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits,tol", [(8, 0.02), (4, 0.1), (2, 0.5)])
def test_matmul_dw_noise_scales_with_bits(bits, tol):
    x = jax.random.normal(KEY, (64, 32))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (32, 8))
    pol = ACTPolicy(bits=bits)
    gw = jax.grad(lambda w_: (act_matmul(
        x, w_, key=KEY, policy=pol) ** 2).sum())(w)
    exact = jax.grad(lambda w_: ((x @ w_) ** 2).sum())(w)
    rel = float(jnp.abs(gw - exact).max() / jnp.abs(exact).max())
    assert rel < tol, rel


def test_dw_unbiased_across_keys():
    """Averaging ∇w over many SR draws converges to the exact gradient."""
    x = jax.random.normal(KEY, (32, 16))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (16, 4))
    exact = jax.grad(lambda w_: ((x @ w_) ** 2).sum())(w)
    pol = ACTPolicy(bits=2)
    keys = jax.random.split(jax.random.fold_in(KEY, 2), 1500)
    gws = jax.vmap(lambda k: jax.grad(lambda w_: (act_matmul(
        x, w_, key=k, policy=pol) ** 2).sum())(w))(keys)
    rel = float(jnp.abs(gws.mean(0) - exact).max() / jnp.abs(exact).max())
    assert rel < 0.03, rel


def test_relu_mask_is_exact():
    x = jax.random.normal(KEY, (128,))
    g = jax.grad(lambda x_: (act_relu(x_) ** 3).sum())(x)
    e = jax.grad(lambda x_: (jnp.maximum(x_, 0) ** 3).sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-6)


@pytest.mark.parametrize("fn", ["silu", "gelu", "tanh", "sigmoid",
                                "leaky_relu"])
def test_nonlin_fp32_matches_autodiff(fn):
    refs = {"silu": jax.nn.silu, "tanh": jnp.tanh,
            "sigmoid": jax.nn.sigmoid,
            "leaky_relu": lambda x: jnp.where(x > 0, x, 0.01 * x),
            "gelu": lambda x: jax.nn.gelu(x, approximate=True)}
    x = jax.random.normal(KEY, (64,))
    g = jax.grad(lambda x_: act_nonlin(x_, key=KEY, policy=FP32,
                                       fn=fn).sum())(x)
    e = jax.grad(lambda x_: refs[fn](x_).sum())(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(e), rtol=1e-3,
                               atol=1e-4)


def test_rmsnorm_grads_match():
    x = jax.random.normal(KEY, (8, 32))
    gamma = jax.random.normal(jax.random.fold_in(KEY, 1), (32,)) + 1.0

    def ref(x_, g_):
        r = jax.lax.rsqrt(jnp.mean(x_ * x_, -1, keepdims=True) + 1e-6)
        return ((x_ * r * g_) ** 2).sum()

    gx, gg = jax.grad(lambda x_, g_: (act_rmsnorm(
        x_, g_, key=KEY, policy=FP32) ** 2).sum(), argnums=(0, 1))(x, gamma)
    ex, eg = jax.grad(ref, argnums=(0, 1))(x, gamma)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ex), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(eg), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.slow
def test_spmm_dx_exact_dew_noisy():
    N, E, d = 30, 150, 16
    src = jax.random.randint(KEY, (E,), 0, N)
    dst = jax.random.randint(jax.random.fold_in(KEY, 1), (E,), 0, N)
    ew = jax.random.uniform(jax.random.fold_in(KEY, 2), (E,))
    x = jax.random.normal(KEY, (N, d))

    def ref(x_, ew_):
        return (jax.ops.segment_sum(x_[src] * ew_[:, None], dst,
                                    num_segments=N) ** 2).sum()

    def act(x_, ew_, pol):
        return (act_spmm(x_, src, dst, ew_, num_nodes=N, key=KEY,
                         policy=pol) ** 2).sum()

    ex, eew = jax.grad(ref, argnums=(0, 1))(x, ew)
    gx, gew = jax.grad(act, argnums=(0, 1))(x, ew, INT8)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ex), rtol=1e-5,
                               atol=1e-5)  # dx needs no activation
    rel = float(jnp.abs(gew - eew).max() / jnp.abs(eew).max())
    assert rel < 0.05, rel  # dew reads the INT8 x̂


def test_act_remat_grad_close_and_fp32_exact():
    w = jax.random.normal(KEY, (32, 32))
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (8, 32))

    def block(p, x_, consts):
        return jnp.tanh(x_ @ p) + x_

    exact = jax.grad(lambda p: block(p, x, None).sum())(w)
    for pol, tol in ((FP32, 1e-6), (INT8, 0.05)):
        f = act_remat(block, pol)
        g = jax.grad(lambda p: f(p, x, KEY).sum())(w)
        rel = float(jnp.abs(g - exact).max() / jnp.abs(exact).max())
        assert rel < tol, (pol.bits, rel)


# -- op sites on the compiled step (DESIGN.md §13) ---------------------------


@pytest.mark.parametrize("arch", ["kgat", "kgin"])
def test_compiled_step_ops_carry_their_site(arch):
    """Every gather, scatter and dot of a tiny INT2 train step names the
    model, the BPR head or the optimizer in its ``op_name``, and the ACT
    quantize/dequantize, the aggregation and (kgat) the attention sites
    all appear."""
    import re

    from repro.configs import get
    from repro.core.policy import schedule_from_cli
    from repro.models.registry import build_step
    from repro.obs.sites import scopes
    from repro.training.optimizer import adam
    from repro.training.step import make_train_step

    sched = schedule_from_cli(None, 2)
    step = build_step(get(arch), schedule=sched, dim=8, n_layers=2)
    opt = adam(1e-3)
    params = step.init(jax.random.PRNGKey(0))
    text = make_train_step(step, opt, schedule=sched, root_key=KEY).lower(
        (params, opt.init(params)), next(step.batches()), 0
    ).compile().as_text()
    seen, checked = set(), 0
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            seen |= set(scopes(m.group(1)))
        if re.search(r"= \S+ (gather|scatter|dot)\(", line):
            assert m and set(scopes(m.group(1))) & {arch, "bpr",
                                                    "optimizer"}, line
            checked += 1
    assert checked > 10
    want = {"act_quant", "act_dequant", "spmm", "bpr", "optimizer"}
    assert want | ({"attention"} if arch == "kgat" else set()) <= seen

