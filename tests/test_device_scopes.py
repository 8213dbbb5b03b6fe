"""Device time by part of the program (ISSUE 37): the named scopes the
models' and the engine's traced code opens, ``profiler.device_table``
that reads them from a trace's own ``tf_op`` records, the "Device time
by scope" table of ``profiler.dumps()``, and the benchmark's reducer of
both (``benchmark/lib/reducers/device_by_scope.py``)."""
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "examples")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import generate, profiler  # noqa: E402
from mxnet_tpu.profiler import DeviceOp  # noqa: E402
from benchmark.lib import manifest, xplane  # noqa: E402
from benchmark.lib.reducers import device_by_scope  # noqa: E402

RECORDED = os.path.join(ROOT, "tests", "data", "small_engine.xplane.pb")
MS = 1e6  # ns


# -- (a) the reader, on a trace recorded on the chip --------------------------
#
# `python tools/profile_scopes.py` on one TPU v5 lite chip (PR 37): the
# small expert engine's prefill chunk of 16 and one (4, 1) decode step
# (and the two programs of a few operations that feed a first token and
# mask the feed), the host's planes taken out to keep the file small.

@pytest.fixture(scope="module")
def recorded():
    assert os.path.getsize(RECORDED) < 1 << 20
    return profiler.device_table(RECORDED)


def test_recorded_trace_known_operations_land_in_their_scopes(recorded):
    by_part = {}
    for op in recorded:
        by_part.setdefault(profiler.part_scope(op.scope), []).append(op)
    # every part the small expert decoder has, and no other
    assert set(by_part) - {None} == {
        "cache.gather", "cache.write", "sample", "embed", "head",
        "attn.proj", "attn.core", "experts.route", "experts.ffn"}
    # the grouped kernels are the expert layers' product, two a layer
    # a program: 2 layers x 2 kernels x (a chunk + a step)
    kernels = [op for op in by_part["experts.ffn"]
               if "custom-call" in op.name]
    assert len(kernels) == 8
    assert {re.match(r"%(\w+?)[.\d]* =", op.name).group(1)
            for op in kernels} == {"grouped_ffn_gate_up",
                                   "grouped_ffn_down_combine"}
    # source files are named from the checkout's root
    assert {op.source.split(":")[0] for op in kernels} == \
        {"mxnet_tpu/parallel/moe.py"}
    # the pool's gathers are traced in the engine, the head's product (a
    # convolution to the compiler) in the model's `_run`
    assert {op.source.split(":")[0] for op in by_part["cache.gather"]
            if op.source} == {"mxnet_tpu/generate.py"}
    product = [op for op in by_part["head"] if "convolution" in op.name]
    assert len(product) == 2 and all(
        op.source.startswith("mxnet_tpu/gluon/model_zoo/language/"
                             "moe_decoder.py:") and op.flops > 0
        for op in product)
    # an operation the compiler added is lent its first reader's scope:
    # the wait for the head's (256, 128) float32 matrix is the head's
    waits = [op for op in recorded
             if op.name.startswith("%copy-done = f32[256,128]")]
    assert len(waits) == 2 and all(
        op.scope == ("head",) and not op.source
        and op.hlo_category == "copy-done" for op in waits)
    # what no executed operation reads stays unscoped
    assert all(not op.source or "generate.py" in op.source
               or "moe_decoder.py" in op.source for op in by_part[None])
    assert sum(op.duration_ns for op in by_part[None]) < \
        0.12 * sum(op.duration_ns for op in recorded)
    assert all(op.bytes_accessed >= 0 and op.duration_ns >= 0
               and op.device == 0 for op in recorded)


def test_recorded_trace_durations_sum_to_the_runs(recorded):
    """Four runs (a chunk's program, a step's, the two that feed), told
    apart by their starts; the operations of each add up to the run's own
    time less the few microseconds between operations."""
    planes = xplane.load(RECORDED)
    runs = sorted(planes[xplane.RUNS]["device:0"], key=lambda r: r[1])
    starts = sorted({op.run_start_ns for op in recorded})
    assert len(runs) == len(starts) == 4 and None not in starts
    programs = set()
    for (_run_id, start, dur), mine in zip(runs, starts):
        assert abs(start - mine) < 2.0
        ops = [op for op in recorded if op.run_start_ns == mine]
        programs.add(ops[0].program_id)
        assert len({op.program_id for op in ops}) == 1
        busy = sum(op.duration_ns for op in ops)
        assert 0.4 * dur < busy <= dur + 1.0
        assert all(start - 1 <= op.start_ns <= start + dur for op in ops)
    assert len(programs) == 4
    # the same numbers as jax's reader gives, operation for operation
    theirs = sorted(planes["/device:TPU:0"][xplane.OPS_LINE],
                    key=lambda e: e[1])
    assert len(theirs) == len(recorded)
    for (_name, start, dur), op in zip(theirs, recorded):
        assert abs(start - op.start_ns) < 1.0 and abs(dur - op.duration_ns) \
            < 1.0


# -- (b) the normaliser -------------------------------------------------------

@pytest.mark.parametrize("tf_op,want", [
    ("jit(chunk_fn)/jit(main)/experts.ffn/dot_general:", ("experts.ffn",)),
    ("jit(step)/transpose(jvp(_resnet0__unit0_batchnorm0))/mul",
     ("_resnet0__unit0_batchnorm0",)),
    ("jit(chunk_fn)/draft/attn.core/bhqd,bhsd->bhqs/dot_general:",
     ("draft", "attn.core")),
    ("", ()),
    ("jit(f)/pjit/ffn/add:", ("ffn",)),
    ("transpose(jvp(jit(step)))/closed_call/head/dot_general", ("head",)),
    # a function jitted once and shared: its operations carry no caller
    ("experts.route/gather", ("experts.route",)),
    ("jit(chunk_fn)/kda.scan/while/body/mul:", ("kda.scan",)),
    ("jit(chunk_fn)/experts.ffn/cond/branch_0_fun/grouped_ffn_gate_up/"
     "pallas_call", ("experts.ffn", "grouped_ffn_gate_up")),
    # operations the compiler merged: the first name is read
    ("jit(chunk_fn)/cache.write/broadcast_in_dim;jit(chunk_fn)/attn.proj/"
     "transpose", ("cache.write",)),
    ("jit(run)/jit(_take)/and:", ()),
])
def test_scope_path_of_a_tf_op(tf_op, want):
    assert profiler.scope_path(tf_op) == want


def test_part_scope_is_the_innermost_of_the_vocabulary():
    assert profiler.part_scope(("draft", "attn.core")) == "attn.core"
    assert profiler.part_scope(("draft",)) == "draft"
    assert profiler.part_scope(
        ("attn.proj", "transformerlm0_h0_proj_q")) == "attn.proj"
    assert profiler.part_scope(("resnetv10_batchnorm0",)) is None
    assert profiler.part_scope(()) is None
    assert not [n for n in profiler.PART_SCOPES if re.search(r"\d", n)]


# -- (c) the benchmark's reducer, on a hand-made trace ------------------------

def _op(start_ms, dur_ms, scope, program=1, source=""):
    return DeviceOp(program, None, start_ms * MS, dur_ms * MS, scope,
                    source, "fusion", 1000, 10, "%fusion.1 = f32[8]", 0)


def _ctx():
    """Three decode steps and a chunk.  The host launches at 11, 21, 41
    and 51 ms inside spans that end 2 ms later; the device runs the
    programs at 12-18 (past its span's end: charged to it all the same),
    22-30 (the chunk), 42-47 and 52-53 ms (the last charged span: left
    out)."""
    spans = [("mx:engine.decode", 10, 4), ("mx:engine.decode:launch", 11, 1),
             ("mx:engine.prefill", 20, 4), ("mx:engine.decode", 40, 4),
             ("mx:engine.decode", 50, 4), ("mx:serve.tick", 9, 50)]
    host = [(n, s * MS, d * MS) for n, s, d in spans]
    host += [(xplane.LAUNCH, t * MS, 0.2 * MS) for t in (11, 21, 41, 51)]
    runs = {"enqueued": [(str(i), (t + 0.5) * MS, 0.1 * MS)
                         for i, t in enumerate((11, 21, 41, 51))],
            "device:0": [("0", 12 * MS, 6 * MS), ("1", 22 * MS, 8 * MS),
                         ("2", 42 * MS, 5 * MS), ("3", 52 * MS, 1 * MS)]}
    ops = [
        # step 1 (program 1): 2 + 3 + 0.5 unscoped + 0.25 under a block
        _op(12, 2, ("cache.gather",)), _op(14, 3, ("experts.ffn",)),
        _op(17, 0.5, (), source=manifest.ROOT + "/mxnet_tpu/generate.py:7"),
        _op(17.5, 0.25, ("head", "dense0")),
        # the chunk (program 2)
        _op(22, 6, ("draft", "experts.ffn"), program=2),
        _op(28, 2, ("cache.gather",), program=2),
        # step 2
        _op(42, 1, ("cache.gather",)), _op(43, 4, ("experts.ffn",)),
        # step 3: never read
        _op(52, 1, ("cache.gather",))]
    return {"planes": {"/host:CPU": {"main": host},
                       "/device:TPU:0": {xplane.OPS_LINE: []},
                       xplane.RUNS: runs},
            "window": {}, "_device_ops": {0: ops}}


@pytest.mark.parametrize("args,want", [
    ({"scope": "cache.gather", "span": "mx:engine.decode"}, (2 + 1) / 2),
    ({"scope": "experts.ffn", "span": "mx:engine.decode"}, (3 + 4) / 2),
    ({"scope": "head", "span": "mx:engine.decode"}, 0.25 / 2),
    ({"scope": "experts.ffn", "span": "mx:engine.prefill"}, 6.0),
    ({"scope": "cache.gather", "span": "mx:engine.prefill"}, 2.0),
    ({"span": "mx:engine.decode", "share": True},
     100 * 0.5 / (2 + 3 + 0.5 + 0.25 + 1 + 4)),
    ({"scope": "kda.scan", "span": "mx:engine.decode"}, None),
])
def test_reducer_charges_runs_to_the_spans_that_launched_them(
        args, want, capsys):
    ctx = _ctx()
    got = device_by_scope.reduce(ctx, **args)
    assert got == (None if want is None else pytest.approx(want))
    err = capsys.readouterr().err
    # the whole table, once a run: every scope, and the unscoped rest by
    # the line that traced it
    assert "mx:engine.decode: 2 spans launched a program" in err
    assert "5.5000 ms a span" in err          # the runs: (6 + 5) / 2
    assert re.search(r"experts\.ffn\s+3\.5000 ms a span", err)
    assert re.search(r"unscoped: mxnet_tpu/generate\.py:7\s+0\.2500", err)
    assert "mx:engine.prefill: 1 spans" in err
    device_by_scope.reduce(ctx, **args)
    assert capsys.readouterr().err == ""


def test_reducer_finds_nothing_without_scopes_or_a_trace(capsys):
    """The parent's program opens no scope and has no ``device_table``:
    every metric is left out of its line, and nothing raises."""
    args = {"scope": "head", "span": "mx:engine.decode"}
    assert device_by_scope.reduce({"planes": None, "window": {}},
                                  **args) is None
    bare = _ctx()
    bare["_device_ops"] = {0: [op._replace(scope=())
                               for op in bare["_device_ops"][0]]}
    assert device_by_scope.reduce(bare, **args) is None
    assert device_by_scope.reduce(bare, span="mx:engine.decode",
                                  share=True) is None
    none = dict(_ctx(), _device_ops=None)
    assert device_by_scope.reduce(none, **args) is None
    assert device_by_scope.reduce(none, match="batchnorm",
                                  steps_key="traced_steps") is None


def test_reducer_sums_a_block_kind_over_the_traced_steps(capsys):
    ops = [_op(1, 3, ("resnetv10", "resnetv10_stage1_batchnorm0")),
           _op(4, 5, ("resnetv10", "resnetv10_conv0")),
           _op(9, 1, ("resnetv10", "resnetv10_batchnorm12")), _op(10, 1, ())]
    ctx = {"planes": {"x": {}}, "window": {"traced_steps": 2},
           "_device_ops": {0: ops}}
    assert device_by_scope.reduce(
        ctx, match="batchnorm", steps_key="traced_steps") == \
        pytest.approx((3 + 1) / 2)
    err = capsys.readouterr().err
    assert re.search(r"batchnorm\s+2\.0000 ms a step\s+40\.00 %", err)
    assert re.search(r"conv\s+2\.5000 ms a step", err)
    assert device_by_scope.reduce(
        ctx, match="layernorm", steps_key="traced_steps") is None


SCOPE_METRICS = {
    "decode_cache_gather_device_ms_per_step": ("cache.gather", 4),
    "decode_attn_core_device_ms_per_step": ("attn.core", 4),
    "decode_experts_ffn_device_ms_per_step": ("experts.ffn", 3),
    "decode_ffn_device_ms_per_step": ("ffn", 3),
    "decode_head_device_ms_per_step": ("head", 4),
    "decode_state_scan_device_ms_per_step": ("kda.scan", 1),
    "decode_device_unscoped_share": (None, 4),
    "prefill_attn_core_device_ms_per_chunk": ("attn.core", 2),
    "prefill_experts_ffn_device_ms_per_chunk": ("experts.ffn", 3),
    "prefill_state_scan_device_ms_per_chunk": ("kda.scan", 1),
    "train_norm_device_ms_per_step": (None, 1),
}


def test_manifest_lists_the_eleven_after_what_it_had():
    import inspect

    man = manifest.manifest()
    assert manifest.check(man)
    # (later PRs appended more after them, and their cells to the
    # lists of the metrics whose scopes their programs open)
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(next(iter(SCOPE_METRICS)))
    assert at == 40 and names[at:at + 11] == list(SCOPE_METRICS)
    for m in man["per_layer"][at:at + 11]:
        scope, cells = SCOPE_METRICS[m["name"]]
        spec = manifest.layer_metric(m["name"])
        assert spec["reducer"] == "device_by_scope"
        assert spec["args"].get("scope") == scope
        assert scope is None or scope in profiler.PART_SCOPES
        inspect.signature(device_by_scope.reduce).bind({}, **spec["args"])
        assert (m["source"], m["better"]) == ("device_trace", "lower")
        assert len(m["workloads"]) >= cells
        assert m["moves"] == ("train_throughput" if m["name"].startswith(
            "train") else "serve_itl_p95_ms")
        assert m["layer"] == {"experts.ffn": "experts"}.get(
            scope, "device" if m["unit"] == "%" else "kernels")
        # with no trace the reader finds nothing and is silent
        assert device_by_scope.reduce({"window": {}, "planes": None},
                                      **spec["args"]) is None


# -- (d) the scopes are on the programs, without a chip -----------------------

def _opt():
    from transformer_lm import TransformerLM

    net = TransformerLM(vocab_size=48, d_model=32, n_heads=2, n_layers=2,
                        max_len=24)
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(np.zeros((1, 4), np.float32)))
    return net, dict(cache_len=24, page_size=4, prefill_chunk=8)


def _moe():
    from mxnet_tpu.gluon.model_zoo.language import MoEDecoderLM

    net = MoEDecoderLM(64, 32, 2, 4, 2, 16, 4, 2, 16, block_length=4,
                       mask_token_id=63, max_len=32)
    net.initialize(mx.init.Normal(0.02))
    return net, dict(cache_len=32, page_size=4, prefill_chunk=8,
                     denoise_steps=2)


def _hybrid(**kw):
    from mxnet_tpu.gluon.model_zoo.language import HybridDecoderLM

    net = HybridDecoderLM(
        vocab_size=64, d_model=32, n_heads=2, d_k=8, d_v=8, conv_kernel=4,
        kda_lower_bound=-5, d_nope=8, d_rope=4, d_latent=16, d_ff=32,
        n_experts=8, top_k=2, d_expert=16, n_group=4, topk_group=2,
        routed_scaling=2.5, max_len=64, **kw)
    net.initialize(mx.init.Normal(0.02))
    return net


def _ling():
    return _hybrid(mixers=["kda", "mla"], ffns=["dense", "moe"]), \
        dict(cache_len=64, page_size=8, prefill_chunk=16,
             prefix_share=False)


def _giga():
    return _hybrid(mixers=["mla", "mla"], ffns=["dense", "moe"],
                   draft_layers=1), \
        dict(cache_len=64, page_size=8, prefill_chunk=16, spec_k=1,
             prefix_share=False)


ENGINE = "cache.gather cache.write sample embed head".split()
PARTS = {
    "opt": (_opt, ENGINE + ["attn.proj", "attn.core", "ffn"]),
    "moe": (_moe, ENGINE + ["attn.proj", "attn.core", "experts.route",
                            "experts.ffn"]),
    "ling": (_ling, ENGINE + ["attn.proj", "attn.core", "kda.proj",
                              "kda.scan", "ffn", "experts.route",
                              "experts.ffn"]),
    "giga": (_giga, ENGINE + ["attn.proj", "attn.core", "ffn",
                              "experts.route", "experts.ffn", "draft"]),
}
_ENGINES = {}


@pytest.mark.parametrize("shape", ["decode", "prefill"])
@pytest.mark.parametrize("model", sorted(PARTS))
def test_lowered_dispatch_names_every_part_the_model_has(model, shape):
    """The lowered text of the engine's dispatch (nothing compiles or
    runs): every part scope of the vocabulary that the model has is on
    some operation, no other is, and what else is on the name stack is a
    Gluon block's own name (the only names that may hold a digit)."""
    build, parts = PARTS[model]
    if model not in _ENGINES:
        net, kw = build()
        _ENGINES[model] = generate.PagedGenerationEngine(
            net, slots=2, sampling=generate.SamplingConfig(greedy=True),
            **kw)
    eng = _ENGINES[model]
    shapes = eng.dispatch_shapes()
    # a self-drafting engine's step is its verify step
    dispatch = shapes[0] if shape == "prefill" else \
        (shapes[2] if model == "giga" else shapes[1])
    text = eng._jit_chunk.lower(*eng._dispatch_args(dispatch)).as_text(
        debug_info=True)
    # a location is named `<name stack>/<primitive>`, a call's by the
    # name stack alone: every component is kept here
    names = [name for name in re.findall(r'loc\("([^"]+)"', text)
             if name.startswith(("jit(", "experts."))]
    found = {part for name in names
             for part in profiler.scope_path(name + "/_")}
    assert found & set(profiler.PART_SCOPES) == set(parts)
    # a layer's index is in no scope's name: what else holds a digit,
    # the last component (a primitive's name) apart, is a Gluon block's
    # own name (the OPT-like model alone has blocks)
    numbered = {part for name in names
                for part in profiler.scope_path(name)
                if part not in profiler.PART_SCOPES
                and re.search(r"\d", part)}
    assert all(model == "opt" and re.match(r"^transformerlm\d+_\w+$", part)
               for part in numbered), numbered
    if model == "opt":
        assert "transformerlm0_h1_ffn_up" in found
    if model == "giga":       # the draft block's parts nest inside it
        assert any("/draft/attn.core/" in name for name in names)


def test_compile_cache_key_holds_the_names_a_profile_is_read_by():
    """An executable loaded from the persistent cache carries the names
    it was compiled with: the key includes them, source files named from
    the checkout's root (``config.enable_compile_cache``)."""
    import jax

    assert jax.config.jax_compilation_cache_include_metadata_in_key
    regex = jax.config.jax_hlo_source_file_canonicalization_regex
    assert re.sub(regex, "", os.path.join(ROOT, "mxnet_tpu", "generate.py")) \
        == os.path.join("mxnet_tpu", "generate.py")
    assert re.sub(regex, "", "/elsewhere" + ROOT + "/x.py") == \
        "/elsewhere" + ROOT + "/x.py"


# -- (e) dumps() -------------------------------------------------------------

def test_dumps_after_a_cpu_session_says_no_device_plane(tmp_path):
    import jax.numpy as jnp

    was = dict(profiler._config), dict(profiler._state)
    try:
        profiler.set_config(filename=str(tmp_path / "p.json"))
        profiler.start()
        jnp.ones((8, 8)).sum().block_until_ready()
        profiler.stop()
        text = profiler.dumps()
        assert "Device time by scope: no device plane in the trace" in text
        assert "Profile Statistics:" in text and "Device memory:" in text
        assert profiler.device_table(str(tmp_path / "p_trace")) == []
        assert profiler.device_table(str(tmp_path / "nothing")) == []
    finally:
        profiler._config.clear(), profiler._config.update(was[0])
        profiler._state.update(was[1])


def test_dumps_tabulates_a_device_trace(monkeypatch):
    """The table itself, from the recorded chip trace: a row a part
    scope, sorted by time, shares that add up to 100."""
    monkeypatch.setitem(profiler._state, "dir", RECORDED)
    monkeypatch.setitem(profiler._state, "running", False)
    lines = profiler.dumps().split("\n")
    at = next(i for i, line in enumerate(lines)
              if line.startswith("Device time by scope"))
    rows = []
    for line in lines[at + 2:]:
        if not line:
            break
        rows.append(line.split())
    names = [r[0] for r in rows]
    assert {"experts.ffn", "cache.gather", "head"} <= set(names)
    assert sum(float(r[3]) for r in rows) == pytest.approx(100.0, abs=0.1)
    assert [float(r[2]) for r in rows] == sorted(
        (float(r[2]) for r in rows), reverse=True)
