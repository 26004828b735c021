"""ACT context API: scopes, schedules, scope-keyed SR, traced accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    INT2,
    INT8,
    ActContext,
    act_context,
    act_matmul,
    act_relu,
    scope_key,
    traced_activation_report,
)
from repro.core.act import act_spmm
from repro.core.policy import (
    ACTPolicy,
    PolicySchedule,
    ScheduleRule,
    first_layer_int8_rest_int2,
    parse_schedule,
    scope_layer,
)
from repro.core.quant import act_bytes
from repro.data.synthetic import bpr_batches, gen_kg_dataset
from repro.models import kgnn

KEY = jax.random.PRNGKey(0)


def _tiny_kg(model="kgat", dim=16, n_layers=3):
    ds = gen_kg_dataset(n_users=20, n_items=30, n_attrs=10, seed=0)
    cfg = kgnn.KGNNConfig(
        model=model, n_users=ds.n_users, n_entities=ds.n_entities,
        n_relations=ds.n_relations, dim=dim, n_layers=n_layers,
        readout="concat" if model == "kgat" else "sum")
    g = jax.tree_util.tree_map(jnp.asarray, ds.graph)
    params = kgnn.init_params(jax.random.PRNGKey(0), cfg)
    batch = jax.tree_util.tree_map(jnp.asarray,
                                   next(bpr_batches(ds, 32, seed=1)))
    return ds, cfg, g, params, batch


# --- schedule resolution ---------------------------------------------------


def test_schedule_resolution_order_first_match_wins():
    sched = PolicySchedule(rules=(
        ScheduleRule(policy=ACTPolicy(bits=8), op_kind="spmm"),
        ScheduleRule(policy=ACTPolicy(bits=4), scope="m/layer0/*"),
    ), default=ACTPolicy(bits=2))
    # op_kind rule precedes the scope rule even where both match
    assert sched.resolve("spmm", "m/layer0/spmm").bits == 8
    assert sched.resolve("matmul", "m/layer0/w1").bits == 4
    assert sched.resolve("matmul", "m/layer2/w1").bits == 2


def test_scope_layer_and_dedup_suffix_invisible_to_rules():
    assert scope_layer("kgat/layer2/spmm") == 2
    assert scope_layer("kgat/layer2/spmm#1") == 2
    assert scope_layer("dlrm/bot/fc0") is None
    rule = ScheduleRule(policy=INT8, scope="a/*/b")
    assert rule.matches("matmul", "a/x/b#3")


def test_parse_schedule_forms():
    assert parse_schedule("int8").default.bits == 8
    assert parse_schedule("fp32").default.bits is None
    pre = parse_schedule("first_layer_int8_rest_int2")
    assert pre.resolve("spmm", "kgat/layer0/spmm").bits == 8
    assert pre.resolve("spmm", "kgat/layer2/spmm").bits == 2
    rules = parse_schedule("spmm:*/layer0/*=8,*/layer0/*=4,*=1")
    assert rules.resolve("spmm", "m/layer0/spmm").bits == 8
    assert rules.resolve("matmul", "m/layer0/w1").bits == 4
    assert rules.resolve("matmul", "m/layer1/w1").bits == 1
    # rule specs without an explicit *=bits compress ONLY the named sites
    spmm_only = parse_schedule("spmm:*=8")
    assert spmm_only.resolve("spmm", "m/layer1/spmm").bits == 8
    assert spmm_only.resolve("matmul", "m/layer1/w1").bits is None
    with pytest.raises(ValueError):
        parse_schedule("nonsense spec")


# --- mixed per-layer bits land at the right sites (via trace records) ------


def test_mixed_schedule_per_site_bits_in_trace():
    _, cfg, g, params, batch = _tiny_kg()
    ctx = ActContext(first_layer_int8_rest_int2(), KEY)
    with ctx:
        jax.eval_shape(lambda p: kgnn.bpr_loss(p, g, batch, cfg), params)
    by_scope = {r.scope: r.bits for r in ctx.records}
    # 3 layers x (spmm + w1 + w2 + act1 + act2)
    assert len(by_scope) == 15
    layer0 = {k: v for k, v in by_scope.items() if "/layer0/" in k}
    rest = {k: v for k, v in by_scope.items() if "/layer0/" not in k}
    assert layer0 and set(layer0.values()) == {8}
    assert rest and set(rest.values()) == {2}


# --- jax named scopes: metadata only ---------------------------------------


def test_named_scopes_move_no_scope_path_and_no_bit(monkeypatch):
    """The op-site ``jax.named_scope``s only label compiled ops: a tiny
    kgat INT2 step records the same scope paths, and its loss and
    gradients are bit-identical to a build with every named scope off."""
    import contextlib

    _, cfg, g, params, batch = _tiny_kg(dim=8, n_layers=2)
    root = jax.random.PRNGKey(7)

    def build():
        ctx = ActContext(INT2, root, step=3)

        def loss(p):
            with ctx:
                return kgnn.bpr_loss(p, g, batch, cfg)

        return ctx, jax.jit(jax.value_and_grad(loss))(params)

    ctx, (loss, grads) = build()
    assert [(r.scope, r.op_kind) for r in ctx.records] == [
        ("kgat/layer0/spmm", "spmm"), ("kgat/layer0/w1", "matmul"),
        ("kgat/layer0/w2", "matmul"), ("kgat/layer0/act1", "nonlin"),
        ("kgat/layer0/act2", "nonlin"), ("kgat/layer1/spmm", "spmm"),
        ("kgat/layer1/w1", "matmul"), ("kgat/layer1/w2", "matmul"),
        ("kgat/layer1/act1", "nonlin"), ("kgat/layer1/act2", "nonlin")]
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, (loss0, grads0) = build()
    assert bare.records == ctx.records
    assert np.asarray(loss).tobytes() == np.asarray(loss0).tobytes()
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads0)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# --- explicit kwargs vs context: bit-identical grads -----------------------


def test_context_vs_explicit_kwargs_grads_bit_identical():
    _, cfg, g, params, batch = _tiny_kg()
    root = jax.random.PRNGKey(7)

    def loss_ctx(p):
        with act_context(INT2, root):
            return kgnn.bpr_loss(p, g, batch, cfg)

    g_ctx = jax.grad(loss_ctx)(params)
    g_exp = jax.grad(lambda p: kgnn.bpr_loss(
        p, g, batch, cfg, policy=INT2, key=root))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g_ctx),
                    jax.tree_util.tree_leaves(g_exp)):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_op_level_context_matches_explicit_scope_key():
    x = jax.random.normal(KEY, (16, 32))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (32, 8))
    root = jax.random.PRNGKey(11)

    def loss_ctx(w_):
        with act_context(INT2, root, step=3):
            return (act_matmul(x, w_, scope="site") ** 2).sum()

    g_ctx = jax.grad(loss_ctx)(w)
    g_exp = jax.grad(lambda w_: (act_matmul(
        x, w_, key=scope_key(root, "site", 3), policy=INT2) ** 2).sum())(w)
    assert (np.asarray(g_ctx) == np.asarray(g_exp)).all()


# --- scope-keyed SR: replay determinism + stability under op insertion -----


def test_checkpoint_replay_determinism_across_fresh_contexts():
    """Simulated restart: a replayed step reproduces identical grads."""
    _, cfg, g, params, batch = _tiny_kg()
    root = jax.random.PRNGKey(5)

    def grads_at_step(step):
        def loss(p):
            with act_context(INT2, root, step=step):
                return kgnn.bpr_loss(p, g, batch, cfg)
        return jax.grad(loss)(params)

    g_a, g_b = grads_at_step(4), grads_at_step(4)  # "restart" = fresh trace
    for a, b in zip(jax.tree_util.tree_leaves(g_a),
                    jax.tree_util.tree_leaves(g_b)):
        assert (np.asarray(a) == np.asarray(b)).all()
    g_next = grads_at_step(5)
    assert any((np.asarray(a) != np.asarray(b)).any()
               for a, b in zip(jax.tree_util.tree_leaves(g_a),
                               jax.tree_util.tree_leaves(g_next)))


def test_scope_keys_stable_under_op_insertion():
    """Adding an op must not re-key other sites (the KeyChain failure)."""
    x = jax.random.normal(KEY, (8, 16))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (16, 16))
    root = jax.random.PRNGKey(13)

    def run(insert_extra):
        with act_context(INT2, root):
            if insert_extra:
                act_matmul(x, w, scope="extra")  # new op before "site"
            return act_matmul(x, w, scope="site")

    # forward is exact either way; compare the residual keys via grads
    def gw(insert_extra):
        def loss(w_):
            with act_context(INT2, root):
                if insert_extra:
                    (act_matmul(x, w_, scope="extra") ** 2).sum()
                return (act_matmul(x, w_, scope="site") ** 2).sum()
        return jax.grad(loss)(w)

    assert (np.asarray(gw(False)) == np.asarray(gw(True))).all()


def test_repeated_scope_names_get_distinct_keys():
    ctx = ActContext(INT2, KEY)
    with ctx:
        a = ctx.qualify("s")
        b = ctx.qualify("s")
    assert a == "s" and b == "s#1"
    assert not np.array_equal(np.asarray(ctx.key_for(a)),
                              np.asarray(ctx.key_for(b)))


# --- key-required regression (no silent PRNGKey(0) fallback) ---------------


def test_propagate_requires_key_under_stochastic_policy():
    _, cfg, g, params, _ = _tiny_kg()
    with pytest.raises(ValueError, match="key"):
        kgnn.propagate(params, g, cfg, policy=INT2)
    # nearest rounding / FP32 need no key
    kgnn.propagate(params, g, cfg,
                   policy=ACTPolicy(bits=2, stochastic=False))
    kgnn.propagate(params, g, cfg)


def test_op_requires_key_under_stochastic_policy():
    x = jax.random.normal(KEY, (4, 8))
    w = jax.random.normal(KEY, (8, 4))
    with pytest.raises(ValueError, match="key"):
        act_matmul(x, w, policy=INT2)
    # linear spmm needs no key even under an active stochastic policy
    src = jnp.array([0, 1, 2], jnp.int32)
    dst = jnp.array([1, 2, 3], jnp.int32)
    act_spmm(x, src, dst, None, num_nodes=4, policy=INT2)


# --- traced memory accounting ----------------------------------------------


@pytest.mark.parametrize("model,per_layer", [("kgat", 5), ("kgcn", 3)])
def test_traced_int2_report_matches_hand_totals(model, per_layer):
    """Uniform INT2: trace == the pre-redesign hand-computed totals.

    The deleted activation_shapes tables priced per layer: spmm input E
    plus 4 (kgat) / 2 (kgcn) transform/nonlin inputs, all (n_nodes, dim)
    at dim_in == dim_out. (For KGIN the hand table was already wrong —
    it priced a phantom spmm residual — which is the point of tracing.)
    """
    _, cfg, g, params, batch = _tiny_kg(model=model)
    rep = traced_activation_report(
        lambda p: kgnn.bpr_loss(p, g, batch, cfg), params, schedule=INT2)
    n, d = cfg.n_nodes, cfg.dim
    hand_total = cfg.n_layers * per_layer * act_bytes((n, d), 2)
    hand_fp32 = cfg.n_layers * per_layer * act_bytes((n, d), None)
    assert rep["total_bytes"] == hand_total
    assert rep["total_fp32_bytes"] == hand_fp32


def test_traced_report_prices_mixed_schedule():
    _, cfg, g, params, batch = _tiny_kg()
    rep8 = traced_activation_report(
        lambda p: kgnn.bpr_loss(p, g, batch, cfg), params, schedule=INT8)
    rep2 = traced_activation_report(
        lambda p: kgnn.bpr_loss(p, g, batch, cfg), params, schedule=INT2)
    mix = traced_activation_report(
        lambda p: kgnn.bpr_loss(p, g, batch, cfg), params,
        schedule=first_layer_int8_rest_int2())
    assert rep2["total_bytes"] < mix["total_bytes"] < rep8["total_bytes"]
    # layer0 priced at INT8, deeper layers at INT2
    assert mix["kgat/layer0/spmm"] == rep8["kgat/layer0/spmm"]
    assert mix["kgat/layer2/spmm"] == rep2["kgat/layer2/spmm"]


def test_repeated_model_calls_under_one_trace_dedup_scopes():
    """Two explicit-kwarg model calls under one recording context must get
    distinct (#k-suffixed) sites — unique SR keys, no silently overwritten
    report entries."""
    _, cfg, g, params, _ = _tiny_kg(n_layers=1)
    ctx = ActContext(INT2, KEY)
    with ctx:
        kgnn.propagate(params, g, cfg, policy=INT2, key=KEY)
        kgnn.propagate(params, g, cfg, policy=INT2, key=KEY)
    scopes = [r.scope for r in ctx.records]
    assert len(scopes) == len(set(scopes))
    assert "kgat/layer0/spmm" in scopes and "kgat/layer0/spmm#1" in scopes


def test_explicit_key_override_still_feeds_outer_trace():
    """An explicit key= forces a local context; its records must still
    land in the ambient (recording) context's trace."""
    _, cfg, g, params, batch = _tiny_kg()
    rep = traced_activation_report(
        lambda p: kgnn.bpr_loss(p, g, batch, cfg, key=jax.random.PRNGKey(5)),
        params, schedule=INT2)
    assert rep["total_bytes"] > 0
    assert "kgat/layer0/spmm" in rep


def test_transformer_scan_records_one_residual_per_layer():
    from repro.models import transformer as tf
    cfg = tf.TransformerConfig(n_layers=4, d_model=32, n_heads=2,
                               n_kv_heads=2, d_ff=64, vocab=97, d_head=16)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((2, 16), jnp.int32)
    rep = traced_activation_report(
        lambda p: tf.lm_loss(p, {"tokens": toks}, cfg), params, schedule=INT2)
    assert sum(1 for k in rep if k.startswith("lm/block")) == cfg.n_layers


def test_two_transformer_forwards_get_distinct_sr_roots():
    """Two forwards (e.g. a two-tower loss) under one recording context
    must not reuse identical rounding noise — the key root derives from a
    #k-deduped site."""
    from repro.models import transformer as tf
    cfg = tf.TransformerConfig(n_layers=2, d_model=32, n_heads=2,
                               n_kv_heads=2, d_ff=64, vocab=97, d_head=16)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((2, 8), jnp.int32)
    ctx = ActContext(INT2, KEY)
    with ctx:
        a = tf.forward(params, toks, cfg)
        b = tf.forward(params, toks, cfg)
    # forward is exact either way; the registered sites must differ so the
    # derived roots (and the recorded residual scopes) differ
    scopes = [r.scope for r in ctx.records]
    assert len(scopes) == len(set(scopes))
    assert "lm" in ctx._seen and ctx._seen["lm"] == 2
    assert not np.array_equal(np.asarray(ctx.key_for("lm")),
                              np.asarray(ctx.key_for("lm#1")))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_act_remat_resolves_policy_at_call_time():
    """A block wrapped OUTSIDE any context must honor the schedule it is
    later applied under (same call-time semantics as every other op)."""
    from repro.core import act_remat

    w = jax.random.normal(KEY, (16, 16))
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 16))
    f = act_remat(lambda p, x_, c: jnp.tanh(x_ @ p), scope="blk")  # no ctx

    exact = jax.grad(lambda p: jnp.tanh(x @ p).sum())(w)

    def loss(p):
        with act_context(INT2, KEY):
            return f(p, x).sum()

    ctx = ActContext(INT2, KEY)
    with ctx:
        f(w, x)
    (r,) = ctx.records
    assert r.scope == "blk" and r.bits == 2  # schedule applied, recorded
    g2 = jax.grad(loss)(w)
    assert not np.allclose(np.asarray(g2), np.asarray(exact))  # INT2 noise
    g_fp = jax.grad(lambda p: f(p, x).sum())(w)  # no ctx -> FP32 baseline
    np.testing.assert_allclose(np.asarray(g_fp), np.asarray(exact),
                               rtol=1e-6, atol=1e-6)


def test_relu_mask_recorded_exact():
    x = jax.random.normal(KEY, (32, 64))
    ctx = ActContext(INT2, KEY)
    with ctx:
        act_relu(x, scope="mask")
    (r,) = ctx.records
    assert r.exact_mask and r.bits == 1
    assert ctx.report()["mask"] == 32 * 8  # 64 bits -> 8 bytes per row
