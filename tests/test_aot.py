"""AOT compilation layer tests (mxnet_tpu.aot + tools/prewarm.py).

The contract under test: serialized executables round-trip with
identical outputs; every failure mode (corrupted/truncated artifact,
version/topology mismatch, malformed store) degrades to a recompile
with a loud warning — never to a wrong answer; the prewarm CLI
populates a store cold and validates it (nonzero on malformed).
Tiny shapes throughout — the whole file must stay well inside the
tier-1 window.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import aot, gluon, nd, parallel
import mxnet_tpu.telemetry as tel
from mxnet_tpu.serving import Predictor
from mxnet_tpu.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREWARM = os.path.join(REPO, "tools", "prewarm.py")


@pytest.fixture
def store(tmp_path):
    return aot.AOTStore(str(tmp_path / "aot"))


@pytest.fixture
def telemetry_on():
    tel.enable()
    tel.reset()
    yield
    tel.reset()
    tel.disable()


def make_fn():
    import jax

    return jax.jit(lambda x, y: x @ y + 1.0)


def args():
    import jax
    import jax.numpy as jnp

    return (jax.device_put(jnp.arange(12.0).reshape(3, 4)),
            jax.device_put(jnp.ones((4, 2))))


def assert_hit_executed(fn):
    """The loaded executable itself served the calls: equal outputs alone
    cannot tell, because a failed dispatch degrades to the plain jit."""
    assert isinstance(fn, aot.AOTFunction) and fn._compiled
    assert all(e is not fn._FALLBACK for e in fn._compiled.values())


# ---------------------------------------------------------------------------
# round-trip + counters
# ---------------------------------------------------------------------------

def test_roundtrip_same_outputs_and_counters(store, telemetry_on):
    x, y = args()
    want = np.asarray(make_fn()(x, y))

    af = aot.AOTFunction(make_fn(), "t:mm", store)
    np.testing.assert_array_equal(np.asarray(af(x, y)), want)
    assert tel.AOT_CACHE_MISSES.value() == 1
    assert tel.AOT_SAVES.value() == 1

    # a fresh wrapper over a fresh jit = a simulated fresh process:
    # must deserialize, not recompile, and produce identical outputs
    af2 = aot.AOTFunction(make_fn(), "t:mm", aot.AOTStore(store.path))
    np.testing.assert_array_equal(np.asarray(af2(x, y)), want)
    assert tel.AOT_CACHE_HITS.value() == 1
    assert tel.AOT_CACHE_MISSES.value() == 1

    # the steady-state path reuses the loaded executable (no new hits)
    np.testing.assert_array_equal(np.asarray(af2(x, y)), want)
    assert tel.AOT_CACHE_HITS.value() == 1
    assert_hit_executed(af2)
    assert tel.AOT_FALLBACKS.value(reason="dispatch") == 0


def test_new_signature_is_a_new_entry(store):
    import jax.numpy as jnp

    af = aot.AOTFunction(make_fn(), "t:mm", store)
    x, y = args()
    af(x, y)
    af(jnp.ones((5, 4)), jnp.ones((4, 2)))  # new shape -> second entry
    assert len(store.entries()) == 2


# ---------------------------------------------------------------------------
# damage degrades to recompile, never wrong answers
# ---------------------------------------------------------------------------

def _one_entry_store(store):
    x, y = args()
    af = aot.AOTFunction(make_fn(), "t:mm", store)
    want = np.asarray(af(x, y))
    (key, _meta), = store.entries()
    return key, want, (x, y)


@pytest.mark.parametrize("damage", ["flip_bit", "truncate"])
def test_corrupted_artifact_recompiles_with_warning(store, damage):
    key, want, (x, y) = _one_entry_store(store)
    getattr(faults, damage if damage == "flip_bit" else "truncate_file")(
        os.path.join(store.path, key + ".bin"))
    with pytest.warns(UserWarning, match="SHA-256"):
        af = aot.AOTFunction(make_fn(), "t:mm", aot.AOTStore(store.path))
        np.testing.assert_array_equal(np.asarray(af(x, y)), want)
    # the recompile re-persisted a good artifact: the store healed
    problems, _stale = aot.AOTStore(store.path).check()
    assert problems == []


def test_malformed_meta_is_a_loud_miss(store):
    key, want, (x, y) = _one_entry_store(store)
    faults.corrupt_file(os.path.join(store.path, key + ".json"))
    with pytest.warns(UserWarning, match="malformed meta"):
        af = aot.AOTFunction(make_fn(), "t:mm", aot.AOTStore(store.path))
        np.testing.assert_array_equal(np.asarray(af(x, y)), want)


def test_version_mismatch_falls_back_to_recompile(store):
    key, want, (x, y) = _one_entry_store(store)
    meta_path = os.path.join(store.path, key + ".json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["fingerprint"]["jax"] = "0.0.1"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.warns(UserWarning, match="built for"):
        af = aot.AOTFunction(make_fn(), "t:mm", aot.AOTStore(store.path))
        np.testing.assert_array_equal(np.asarray(af(x, y)), want)


def test_check_reports_damage_and_staleness(store):
    key, _want, _ = _one_entry_store(store)
    assert aot.AOTStore(store.path).check() == ([], [])
    faults.flip_bit(os.path.join(store.path, key + ".bin"))
    problems, _ = aot.AOTStore(store.path).check()
    assert any("SHA-256" in p for p in problems)


def test_tracer_args_delegate_to_jit(store):
    import jax
    import jax.numpy as jnp

    af = aot.AOTFunction(jax.jit(lambda x: (x ** 2).sum()), "t:sq", store)
    g = jax.grad(lambda x: af(x))(jnp.ones((3,)))  # traces THROUGH af
    np.testing.assert_allclose(np.asarray(g), 2 * np.ones((3,)))


# ---------------------------------------------------------------------------
# runtime threading: executor / trainer / predictor
# ---------------------------------------------------------------------------

def test_executor_aot_matches_plain_bind(store):
    import mxnet_tpu.symbol as sym

    x = sym.var("x")
    w = sym.var("w")
    y = sym.FullyConnected(x, weight=w, no_bias=True, num_hidden=4,
                           name="fc")
    rng = np.random.RandomState(0)
    xv = rng.rand(2, 3).astype(np.float32)
    wv = rng.rand(4, 3).astype(np.float32)

    def run(aot_spec):
        exe = y.simple_bind(grad_req="write", x=(2, 3), w=(4, 3),
                            aot=aot_spec)
        exe.arg_dict["x"]._rebind(xv)
        exe.arg_dict["w"]._rebind(wv)
        out = np.asarray(exe.forward(is_train=False)[0]._data)
        exe.forward(is_train=True)
        exe.backward()
        return out, np.asarray(exe.grad_dict["w"]._data)

    out_plain, grad_plain = run(False)
    out_aot, grad_aot = run(store)
    np.testing.assert_array_equal(out_aot, out_plain)
    np.testing.assert_array_equal(grad_aot, grad_plain)
    # fresh bind in the same process = the restart path: must hit
    tel.enable()
    tel.reset()
    try:
        run(store)
        assert tel.AOT_CACHE_HITS.value() >= 1
        assert tel.AOT_CACHE_MISSES.value() == 0
    finally:
        tel.reset()
        tel.disable()


def _tiny_trainer(aot_spec, wv):
    net = gluon.nn.Dense(2, use_bias=False)
    net.initialize()
    net(nd.array(np.zeros((4, 3), np.float32)))  # materialize shapes
    list(net.collect_params().values())[0].set_data(nd.array(wv))
    loss_fn = gluon.loss.L2Loss()
    return parallel.ShardedTrainer(
        net, lambda o, l: loss_fn(o, l), mesh=None, optimizer="sgd",
        aot=aot_spec, aot_spec="test_tiny")


def test_trainer_prewarm_then_step_matches_plain(store):
    rng = np.random.RandomState(1)
    wv = rng.rand(2, 3).astype(np.float32)
    xb = nd.array(rng.rand(4, 3).astype(np.float32))
    yb = nd.array(rng.rand(4, 2).astype(np.float32))

    plain = _tiny_trainer(False, wv)
    loss_plain = [float(plain.step([xb], yb)) for _ in range(2)]

    tr = _tiny_trainer(store, wv)
    info = tr.prewarm([xb], yb)
    assert info["status"] == "compiled"
    # prewarm must not consume PRNG keys or touch state: the loss
    # trajectory matches an un-prewarmed plain-jit run bit-for-bit
    loss_aot = [float(tr.step([xb], yb)) for _ in range(2)]
    assert loss_aot == loss_plain

    # restart path: same store, fresh trainer -> hit, same trajectory
    tr2 = _tiny_trainer(store, wv)
    assert tr2.prewarm([xb], yb)["status"] == "hit"
    assert [float(tr2.step([xb], yb)) for _ in range(2)] == loss_plain
    assert_hit_executed(tr2._step_fn)


def test_trainer_prewarm_reports_disabled_without_store():
    wv = np.ones((2, 3), np.float32)
    tr = _tiny_trainer(False, wv)
    xb = nd.array(np.zeros((4, 3), np.float32))
    yb = nd.array(np.zeros((4, 2), np.float32))
    assert tr.prewarm([xb], yb)["status"] == "disabled"


def test_predictor_prewarm_and_predict(store):
    pred = Predictor(lambda x, p: x * 2.0, [], chain=2,
                     batch_shape=(4, 3), batch_dtype=np.float32,
                     aot=store)
    infos = pred.prewarm()
    assert [i["status"] for i in infos] == ["compiled"]
    x = np.arange(12.0, dtype=np.float32).reshape(4, 3)
    np.testing.assert_array_equal(list(pred.predict([x]))[0], x * 2.0)

    # fresh replica (the warm-pool / restart path): loads, not compiles
    pred2 = Predictor(lambda x, p: x * 2.0, [], chain=2,
                      batch_shape=(4, 3), batch_dtype=np.float32,
                      aot=aot.AOTStore(store.path))
    assert [i["status"] for i in pred2.prewarm()] == ["hit"]
    np.testing.assert_array_equal(list(pred2.predict([x]))[0], x * 2.0)
    assert_hit_executed(pred2._jit_chain)


def test_predictor_prewarm_requires_pinned_contract(store):
    from mxnet_tpu.base import MXNetError

    pred = Predictor(lambda x, p: x * 2.0, [], chain=2, aot=store)
    with pytest.raises(MXNetError, match="batch contract"):
        pred.prewarm()


# ---------------------------------------------------------------------------
# resolution contract
# ---------------------------------------------------------------------------

def test_resolve_aot_contract(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_AOT", raising=False)
    assert aot.resolve_aot(None) is None          # off by default
    assert aot.resolve_aot(False) is None
    assert aot.resolve_aot("off") is None
    s = aot.resolve_aot(str(tmp_path / "s"))
    assert isinstance(s, aot.AOTStore)
    assert aot.resolve_aot(s) is s
    monkeypatch.setenv("MXNET_AOT", "1")
    assert isinstance(aot.resolve_aot(None), aot.AOTStore)
    with pytest.raises(ValueError):
        aot.resolve_aot(123)


def test_config_enable_aot_override(tmp_path, monkeypatch):
    from mxnet_tpu import config

    monkeypatch.delenv("MXNET_AOT", raising=False)
    config.enable_aot(str(tmp_path / "s"))
    try:
        st = aot.resolve_aot(None)
        assert isinstance(st, aot.AOTStore)
        assert st.path == str(tmp_path / "s")
        config.enable_aot(False)
        assert aot.resolve_aot(None) is None
    finally:
        aot.clear_store()


# ---------------------------------------------------------------------------
# prewarm CLI (subprocess — the real rollout path)
# ---------------------------------------------------------------------------

def test_prewarm_cli_cold_then_warm_then_check(tmp_path):
    sdir = str(tmp_path / "store")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXNET_AOT", None)

    cold = subprocess.run(
        [sys.executable, PREWARM, "--model", "tiny_mlp", "--store", sdir,
         "--json"], stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, env=env, timeout=240)
    assert cold.returncode == 0, cold.stderr
    info = json.loads(cold.stdout.strip().splitlines()[-1])
    assert info["compiled"] >= 2 and info["fallbacks"] == 0
    assert info["cold_seconds"] > 0

    # --check on the populated store: clean
    chk = subprocess.run(
        [sys.executable, PREWARM, "--check", "--store", sdir],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, timeout=120)
    assert chk.returncode == 0, chk.stderr

    # manifest replay in-process (cheap): every recorded spec is warm
    store = aot.AOTStore(sdir)
    entries, problems = store.manifest_entries()
    assert problems == []
    assert {e["spec"] for e in entries} == {"tiny_mlp"}
    assert {e["kind"] for e in entries} == {"trainer", "predictor"}

    # corrupt one payload: --check must exit nonzero and name it
    key = store.entries()[0][0]
    faults.truncate_file(os.path.join(sdir, key + ".bin"))
    bad = subprocess.run(
        [sys.executable, PREWARM, "--check", "--store", sdir],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, timeout=120)
    assert bad.returncode != 0
    assert "SHA-256" in bad.stderr


def test_prewarm_cli_nonzero_on_malformed_store(tmp_path):
    sdir = str(tmp_path / "store")
    os.makedirs(sdir)
    with open(os.path.join(sdir, "deadbeef.json"), "w") as f:
        f.write("{not json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bad = subprocess.run(
        [sys.executable, PREWARM, "--check", "--store", sdir],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, timeout=120)
    assert bad.returncode != 0
    assert "MALFORMED" in bad.stderr

    unknown = subprocess.run(
        [sys.executable, PREWARM, "--model", "no_such_model", "--store",
         sdir], stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, env=env, timeout=120)
    assert unknown.returncode != 0
