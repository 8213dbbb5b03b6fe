"""Trace-guided fusion: pattern registry, shape-keyed cost table,
fusion= threading, and the autotune CLI.

Tier-1 contracts pinned here:

* every registered pattern is numerically equal to its unfused graph
  (forward + gradient + aux/moving-stat flow, train and inference) —
  the parity test parametrizes over ``fusion.list_patterns()`` so a
  pattern registered without a parity chain (``bench_builder``) FAILS
  the suite by construction;
* the cost table suppresses a rewrite on a shape measured slower and
  fires a default-off rewrite on a shape measured faster;
* ``fusion=`` threads through Executor/bind, hybridize, and
  ShardedTrainer with the remat_policy fail-fast contract;
* ``tools/autotune.py --check`` exits nonzero on malformed tables.
"""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import fusion_cost as fc
from mxnet_tpu.symbol import fusion as F
from mxnet_tpu.symbol import symbol as S

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

_R = np.random.RandomState(11)

# small shapes keep the parametrized parity sweep a few seconds total;
# a new pattern without an entry here falls back to its bench_shapes
_PARITY_SHAPES = {
    "conv_bn_relu": (2, 3, 8, 8),
    "norm_act": (2, 4, 6, 6),
    "act_scale_add": (3, 5),
    "add_act": (3, 5),
    "layer_norm_fast": (4, 8),
}


@pytest.fixture(autouse=True)
def _no_ambient_table(monkeypatch):
    # config.get() reads os.environ live: an ambient MXNET_FUSION=off
    # or a real MXNET_FUSION_TUNE table would flip fired-pattern
    # expectations, so pin both alongside the programmatic override
    monkeypatch.delenv("MXNET_FUSION", raising=False)
    monkeypatch.delenv("MXNET_FUSION_TUNE", raising=False)
    fc.clear_cost_table()
    yield
    fc.clear_cost_table()


def _bind_vals(sym, feeds, vals, grad_req="write", fusion="off"):
    import jax.numpy as jnp

    exe = sym.simple_bind(ctx=mx.cpu(), grad_req=grad_req, fusion=fusion,
                          **feeds)
    for n, a in list(exe.arg_dict.items()) + list(exe.aux_dict.items()):
        v = vals.setdefault(
            n, (_R.rand(*a.shape).astype(np.float32) + 0.5))
        a._rebind(jnp.asarray(v))
    return exe


# ---------------------------------------------------------------------------
# registry guard + parity
# ---------------------------------------------------------------------------


def test_registry_guard_every_pattern_is_parity_testable():
    """A pattern registered without a canonical chain (bench_builder +
    shapes + doc) cannot be parity-tested or autotuned — fail loudly
    here instead of silently shipping an unverified rewrite."""
    names = F.list_patterns()
    assert len(names) >= 5, names
    for name in names:
        p = F.get_pattern(name)
        assert callable(p.bench_builder), \
            "pattern %r has no bench_builder (parity/autotune chain)" % name
        assert p.bench_shapes, "pattern %r has no bench_shapes" % name
        assert p.doc, "pattern %r has no doc" % name


@pytest.mark.parametrize("name", F.list_patterns())
def test_pattern_parity_fwd_bwd_train_and_infer(name):
    pattern = F.get_pattern(name)
    shape = _PARITY_SHAPES.get(name, pattern.bench_shapes[0])
    chain, feeds = pattern.bench_builder(shape)
    loss = S._invoke_sym("sum", [chain], {}, name="loss")
    fused, fired = F.apply_fusion(loss, name)
    assert fired, "pattern %r did not match its own chain" % name
    # parameter/aux/output contracts preserved
    assert fused.list_arguments() == loss.list_arguments()
    assert fused.list_auxiliary_states() == loss.list_auxiliary_states()
    assert fused.list_outputs() == loss.list_outputs()

    vals = {}
    exe = _bind_vals(loss, feeds, vals)
    fexe = _bind_vals(fused, feeds, vals)
    for e in (exe, fexe):
        e.forward(is_train=True)
        e.backward()
    np.testing.assert_allclose(fexe.outputs[0].asnumpy(),
                               exe.outputs[0].asnumpy(), atol=1e-4,
                               rtol=1e-4)
    for n in exe.grad_dict:
        np.testing.assert_allclose(fexe.grad_dict[n].asnumpy(),
                                   exe.grad_dict[n].asnumpy(),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg="grad %s" % n)
    for n in exe.aux_dict:  # moving-stat updates flow identically
        np.testing.assert_allclose(fexe.aux_dict[n].asnumpy(),
                                   exe.aux_dict[n].asnumpy(), atol=1e-5,
                                   err_msg="aux %s" % n)
    # inference mode after the train step (uses updated moving stats)
    for e in (exe, fexe):
        e.forward(is_train=False)
    np.testing.assert_allclose(fexe.outputs[0].asnumpy(),
                               exe.outputs[0].asnumpy(), atol=1e-4,
                               rtol=1e-4)


def test_act_scale_add_mul_scalar_branch_parity():
    """The _mul_scalar variant of act_scale_add (static-scalar scale,
    2-input kernel branch) fuses by default — keep it parity-covered
    like the tensor-scale chain the bench_builder exercises."""
    a, res = S.var("data"), S.var("residual")
    y = S._invoke_sym("Activation", [a], {"act_type": "relu"}, name="act0")
    y = S._invoke_sym("_mul_scalar", [y], {"scalar": 2.0}, name="mul0")
    y = S._invoke_sym("broadcast_add", [y, res], {}, name="add0")
    loss = S._invoke_sym("sum", [y], {}, name="loss")
    fused, fired = F.apply_fusion(loss, "act_scale_add")
    assert [f[0] for f in fired] == ["act_scale_add"]

    feeds = {"data": (3, 5), "residual": (3, 5)}
    vals = {}
    exe = _bind_vals(loss, feeds, vals)
    fexe = _bind_vals(fused, feeds, vals)
    for e in (exe, fexe):
        e.forward(is_train=True)
        e.backward()
    np.testing.assert_allclose(fexe.outputs[0].asnumpy(),
                               exe.outputs[0].asnumpy(), rtol=1e-5)
    for n in exe.grad_dict:
        np.testing.assert_allclose(fexe.grad_dict[n].asnumpy(),
                                   exe.grad_dict[n].asnumpy(), rtol=1e-5,
                                   err_msg="grad %s" % n)


# ---------------------------------------------------------------------------
# cost-table gating
# ---------------------------------------------------------------------------


def _table(key, speedup):
    return {"version": 1, "entries": {key: {
        "pattern": key.split("|", 1)[0], "fused_ms": 1.0,
        "unfused_ms": speedup, "speedup": speedup,
        "measured_at": "2026-08-03T00:00:00+00:00"}}}


def test_cost_table_suppresses_rewrite_on_slow_shape():
    """A shape the autotuner measured SLOWER fused must not rewrite
    under the default plan — the acceptance-criteria guard."""
    ln = mx.sym.LayerNorm(mx.sym.var("data"), name="ln0")
    key = fc.shape_key("layer_norm_fast", (4, 8), "float32", axis=-1)
    known = {"data": ((4, 8), np.float32)}

    fc.set_cost_table(_table(key, 0.5))
    fused, fired = F.apply_fusion(ln, "default", known=known)
    assert not fired
    assert F.count_ops(fused, "LayerNorm") == 1

    # same shape measured faster -> the default-off pattern fires
    fc.set_cost_table(_table(key, 1.9))
    fused, fired = F.apply_fusion(ln, "default", known=known)
    assert [f[0] for f in fired] == ["layer_norm_fast"]
    assert F.count_ops(fused, "_contrib_layer_norm_fused") == 1
    assert fired[0][2] == key


def test_cost_table_suppresses_default_on_pattern():
    a, b = mx.sym.var("data"), mx.sym.var("res")
    s = mx.sym.Activation(a + b, act_type="relu", name="r0")
    key = fc.shape_key("add_act", (3, 5), "float32")
    known = {"data": ((3, 5), np.float32), "res": ((3, 5), np.float32)}
    # no table: identical-math pattern fires by default
    fused, fired = F.apply_fusion(s, "default", known=known)
    assert [f[0] for f in fired] == ["add_act"]
    # measured slower: suppressed even though default-on
    fc.set_cost_table(_table(key, 0.8))
    fused, fired = F.apply_fusion(s, "default", known=known)
    assert not fired


def test_unknown_shape_falls_back_to_default_without_failing():
    ln = mx.sym.LayerNorm(mx.sym.var("data"), name="ln0")
    fc.set_cost_table(_table("layer_norm_fast|f32|9x9|ax-1", 9.0))
    # no known shapes -> key is None -> default_on (False) -> no fire,
    # and crucially no error
    fused, fired = F.apply_fusion(ln, "default", known=None)
    assert not fired


def test_env_table_path_and_config_setter(tmp_path, monkeypatch):
    key = fc.shape_key("layer_norm_fast", (4, 8), "float32", axis=-1)
    path = tmp_path / "ct.json"
    fc.save_table(str(path), _table(key, 2.0))
    monkeypatch.setenv("MXNET_FUSION_TUNE", str(path))
    t = fc.current_table()
    assert t is not None and t.speedup(key) == 2.0
    # config.fusion_cost_table overrides the env path
    mx.config.fusion_cost_table(None)
    assert fc.current_table() is None
    mx.config.fusion_cost_table(str(path))
    assert fc.current_table().speedup(key) == 2.0


# ---------------------------------------------------------------------------
# fusion= threading (Executor / hybridize / ShardedTrainer)
# ---------------------------------------------------------------------------


def test_executor_bind_fusion_modes_and_fail_fast():
    a, b = mx.sym.var("data"), mx.sym.var("res")
    loss = mx.sym.sum(mx.sym.Activation(a + b, act_type="relu"))
    feeds = {"data": (3, 5), "res": (3, 5)}
    off = loss.simple_bind(ctx=mx.cpu(), fusion="off", **feeds)
    assert off.fusion_fired == []
    dflt = loss.simple_bind(ctx=mx.cpu(), **feeds)
    assert [f[0] for f in dflt.fusion_fired] == ["add_act"]
    with pytest.raises(ValueError, match="unknown fusion pattern"):
        loss.simple_bind(ctx=mx.cpu(), fusion="not_a_pattern", **feeds)
    # reshape preserves the spec
    r = dflt.reshape(data=(6, 5), res=(6, 5))
    assert [f[0] for f in r.fusion_fired] == ["add_act"]


def test_hybridize_layer_norm_fast_path_parity():
    from mxnet_tpu import gluon

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16), gluon.nn.LayerNorm(),
                gluon.nn.Dense(4))
    net.initialize()
    x = mx.nd.array(_R.rand(4, 8).astype(np.float32))
    ref = net(x).asnumpy()
    key = fc.shape_key("layer_norm_fast", (4, 16), "float32", axis=-1)
    fc.set_cost_table(_table(key, 2.0))
    net.hybridize(fusion="default")
    np.testing.assert_allclose(net(x).asnumpy(), ref, atol=1e-5)


def test_sharded_trainer_fusion_all_trains():
    from mxnet_tpu import gluon, parallel

    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(16), gluon.nn.LayerNorm(),
                gluon.nn.Dense(4))
    net.initialize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = parallel.ShardedTrainer(
        net, lambda o, l: loss_fn(o, l), optimizer="sgd",
        optimizer_params={"learning_rate": 0.1}, fusion="all")
    x = mx.nd.array(_R.rand(8, 6).astype(np.float32))
    y = mx.nd.array(_R.randint(0, 4, 8).astype(np.float32))
    losses = [float(trainer.step([x], y)) for _ in range(8)]
    assert losses[-1] < losses[0], losses
    with pytest.raises(ValueError, match="unknown fusion pattern"):
        parallel.ShardedTrainer(net, lambda o, l: loss_fn(o, l),
                                fusion="typo")


def test_fired_rewrites_are_counted_and_traced():
    from mxnet_tpu import telemetry, tracing

    a, b = mx.sym.var("data"), mx.sym.var("res")
    s = mx.sym.Activation(a + b, act_type="relu", name="r0")
    telemetry.enable()
    tracing.enable()
    try:
        before = telemetry.FUSION_REWRITES.value(pattern="add_act")
        F.apply_fusion(s, "default")
        assert telemetry.FUSION_REWRITES.value(pattern="add_act") == \
            before + 1
        payload = tracing.chrome_trace_payload(include_profiler=False)
        names = [ev["name"] for ev in payload["traceEvents"]
                 if ev.get("cat") == "span"]
        assert "fusion:add_act" in names
    finally:
        tracing.disable()
        tracing.reset()
        telemetry.disable()
        telemetry.reset()


# ---------------------------------------------------------------------------
# microbench + autotune CLI
# ---------------------------------------------------------------------------


def test_microbench_reports_bindable_key():
    res = F.microbench("add_act", (8, 16), iters=1, warmup=1, repeats=1)
    assert res["fired"]
    assert res["key"] == fc.shape_key("add_act", (8, 16), "float32")
    assert res["fused_train_ms"] > 0 and res["unfused_train_ms"] > 0


def test_autotune_check_cli(tmp_path, capsys):
    import autotune

    key = fc.shape_key("layer_norm_fast", (4, 8), "float32", axis=-1)
    good = tmp_path / "good.json"
    fc.save_table(str(good), _table(key, 1.5))
    assert autotune.main(["--check", str(good)]) == 0

    # stale entry: reported, still exit 0
    stale = _table(key, 1.5)
    stale["entries"][key]["measured_at"] = "2020-01-01T00:00:00+00:00"
    stale_p = tmp_path / "stale.json"
    fc.save_table(str(stale_p), stale)
    assert autotune.main(["--check", str(stale_p),
                          "--max-age-days", "30"]) == 0
    assert "STALE" in capsys.readouterr().out

    # malformed cases exit nonzero
    bad_json = tmp_path / "bad.json"
    bad_json.write_text("{not json")
    assert autotune.main(["--check", str(bad_json)]) == 1

    bad_ver = tmp_path / "bad_ver.json"
    bad_ver.write_text(json.dumps({"version": 99, "entries": {}}))
    assert autotune.main(["--check", str(bad_ver)]) == 1

    bad_key = _table(key, 1.5)
    bad_key["entries"]["no pipes here"] = {"pattern": "x", "fused_ms": 1,
                                           "unfused_ms": 1, "speedup": 1}
    bad_key_p = tmp_path / "bad_key.json"
    fc.save_table(str(bad_key_p), bad_key)
    assert autotune.main(["--check", str(bad_key_p)]) == 1

    bad_field = {"version": 1, "entries": {key: {"pattern":
                                                 "layer_norm_fast"}}}
    bad_field_p = tmp_path / "bad_field.json"
    bad_field_p.write_text(json.dumps(bad_field))
    assert autotune.main(["--check", str(bad_field_p)]) == 1


def test_broken_table_at_bind_warns_but_binds(tmp_path, monkeypatch):
    """A corrupt MXNET_FUSION_TUNE file must degrade to no-table
    defaults, never break a bind."""
    p = tmp_path / "broken.json"
    p.write_text("{torn write")
    monkeypatch.setenv("MXNET_FUSION_TUNE", str(p))
    a, b = mx.sym.var("data"), mx.sym.var("res")
    loss = mx.sym.sum(mx.sym.Activation(a + b, act_type="relu"))
    with pytest.warns(UserWarning, match="malformed JSON"):
        exe = loss.simple_bind(ctx=mx.cpu(), data=(3, 5), res=(3, 5))
    assert [f[0] for f in exe.fusion_fired] == ["add_act"]


def test_trace_view_top_ops_and_autotune_ranking(tmp_path, capsys):
    """--top-ops prints the op timeline ranked by total time with est.
    HBM bytes; autotune's --trace replay ranks the same data."""
    import autotune
    import trace_view

    trace = {
        "traceEvents": [
            {"name": "Conv", "ph": "X", "cat": "op", "ts": 0.0,
             "dur": 9000.0, "pid": 1, "tid": 0},
            {"name": "Conv", "ph": "X", "cat": "op", "ts": 10000.0,
             "dur": 9000.0, "pid": 1, "tid": 0},
            {"name": "BN", "ph": "X", "cat": "op", "ts": 20000.0,
             "dur": 1000.0, "pid": 1, "tid": 0},
        ],
        "otherData": {"trace_id": "t", "pid": 1,
                      "xla_costs": {"Conv": {"flops": 1.0,
                                             "bytes_accessed": 512.0}}},
    }
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(trace))
    assert trace_view.main([str(p), "--top-ops", "5"]) == 0
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith(("Conv",
                                                             "BN"))]
    assert lines and lines[0].startswith("Conv")  # ranked by total time
    assert "1024" in lines[0]  # 512 bytes x 2 calls
    rows = autotune.rank_trace_ops(str(p))
    assert rows[0][0] == "Conv" and rows[0][3] == 1024.0
