"""Each driver rehearsed on the CPU at a tiny size through ``--rehearse
1``; and the timed path broken underneath, which has to come out as not
correct: a step that leaves its state unchanged, half of the batch left
out, a served token altered, and the control's precision."""
import json
import os

import pytest

import run as bench_run


def last_line(capsys, workload, seconds="1", trace="0"):
    bench_run.main(["--workload", workload, "--seed", str(2**31 + 11),
                    "--seconds", seconds, "--trace", trace, "--rehearse", "1"])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def check_line(line):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert list(line)[-1] == "compared"
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("workload", ["resnet50_train_b256",
                                      "opt1.3b_serve_chat"])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_is_correct_and_carries_no_rate(capsys, workload, trace):
    line = last_line(capsys, workload, trace=trace)
    check_line(line)
    assert line["correct"] is True
    for value, limit in line["compared"].values():
        assert value <= limit


def test_no_accelerator_is_refused(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "resnet50_train_b256", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_state_left_unchanged_is_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp
    from mxnet_tpu.parallel import ShardedTrainer

    real = ShardedTrainer.step

    def step(self, inputs, label):
        if self.param_arrays is None:
            return real(self, inputs, label)
        before = [jnp.copy(a) for a in self.param_arrays]
        loss = real(self, inputs, label)
        self.param_arrays = before
        return loss

    monkeypatch.setattr(ShardedTrainer, "step", step)
    line = last_line(capsys, "resnet50_train_b256")
    assert line["correct"] is False
    assert line["compared"]["delta_norm_gap_median"][0] > \
        line["compared"]["delta_norm_gap_median"][1]


def test_half_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from mxnet_tpu.parallel import ShardedTrainer

    real = ShardedTrainer.step

    def step(self, inputs, label):
        n = label.shape[0] // 2
        return real(self, [x[:n] for x in inputs], label[:n])

    monkeypatch.setattr(ShardedTrainer, "step", step)
    line = last_line(capsys, "resnet50_train_b256")
    assert line["correct"] is False


def test_an_altered_token_is_not_correct(capsys, monkeypatch):
    from mxnet_tpu.generate import PagedGenerationEngine

    real = PagedGenerationEngine.decode_step
    calls = [0]

    def decode_step(self):
        out = real(self)
        calls[0] += 1
        if calls[0] % 3 == 0:
            v = self.model_config["vocab_size"]
            out = {s: [(t + 1 + v // 2) % v for t in toks]
                   for s, toks in out.items()}
        return out

    monkeypatch.setattr(PagedGenerationEngine, "decode_step", decode_step)
    line = last_line(capsys, "opt1.3b_serve_chat")
    assert line["correct"] is False
    assert line["compared"]["logit_gap_max"][0] > \
        line["compared"]["logit_gap_max"][1]


def test_the_control_is_not_correct():
    """The reference in fp8, put in the program's place, fails the
    training cell's limits at a size a test run can hold; the reference
    itself passes them.  (The bfloat16 witness is judged at the cell's
    own size, below: at batch 8 its noise is averaged over too little.)"""
    import argparse

    from benchmark.drivers import train
    from benchmark.lib import compare, manifest, quant

    man = manifest.manifest()
    cell = manifest.workload(man, "resnet50_train_b256")
    run = bench_run.Run(
        argparse.Namespace(seed=5, seconds=1, trace=0, rehearse=1), man, cell,
        manifest.config(man, cell["config"], rehearse=True),
        manifest.traffic(cell["traffic"], rehearse=True),
        manifest.limits(cell["name"]))
    ref = train.reference(run)
    control = compare.train_numbers(train.reference(run, quant=quant.fp8), ref)
    ok, rows = compare.judge(control, run.limits)
    assert ok is False
    assert rows["grad_norm_gap_median"][0] > rows["grad_norm_gap_median"][1]
    assert compare.judge(compare.train_numbers(ref, ref), run.limits)[0] is True


def _chip_readings(cell):
    from benchmark.lib import manifest

    path = os.path.join(manifest.ROOT, "benchmark", "limits",
                        cell + ".readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.mark.parametrize("cell, who, correct", [
    ("resnet50_train_b256", "program", True),
    ("resnet50_train_b256", "witness_bf16", True),
    ("resnet50_train_b256", "control_fp8", False),
    ("resnet50_train_b256", "fault_half_batch", False),
    ("opt1.3b_serve_chat", "program", True),
    ("opt1.3b_serve_chat", "witness_bf16", True),
    ("opt1.3b_serve_chat", "control_fp8", False)])
def test_chip_readings_judged_by_the_committed_limits(cell, who, correct):
    """What ``tools/limits.py`` read on the chip at the cell's own size,
    judged as a run judges: the program and the bfloat16 witness are
    correct on every seed, the fp8 control and the planted fault on none.
    (Each is judged on the numbers it has: the serving control does not
    decode, so it has no lengths.)"""
    from benchmark.lib import compare, manifest

    limits = manifest.limits(cell)
    rows = _chip_readings(cell)
    assert len(rows) >= 3
    for row in rows:
        have = {k: v for k, v in limits.items() if k in row[who]}
        assert have and compare.judge(row[who], have)[0] is correct, \
            (row["seed"], who)
