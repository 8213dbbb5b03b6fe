"""Model-parallel LSTM — counterpart of the reference's
docs/faq/model_parallel_lstm.md + example/model-parallel (group2ctx:
each LSTM layer's parameters live on a different device group).

TPU-native mapping: group2ctx becomes per-layer PartitionSpec rules on
a `jax.sharding.Mesh`.  Layer 0's matrices shard their OUTPUT features
over the 'mp' axis, layer 1's shard their INPUT features — XLA inserts
the all-gather/reduce-scatter pair between the layers exactly where the
reference moved activations between GPUs, but as ICI collectives inside
one fused step.  Data parallelism composes on the same mesh ('dp').

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/model_parallel_lstm.py --steps 30
Prints per-step losses and "MODEL_PARALLEL_LSTM OK first=... last=...".
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu import nd, gluon, parallel
from mxnet_tpu.gluon import nn, rnn


class TwoLayerLSTM(gluon.HybridBlock):
    """Embedding -> LSTM(l0) -> LSTM(l1) -> vocab projection."""

    def __init__(self, vocab, embed, hidden, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, embed)
            self.l0 = rnn.LSTM(hidden, num_layers=1, layout="NTC",
                               input_size=embed)
            self.l1 = rnn.LSTM(hidden, num_layers=1, layout="NTC",
                               input_size=hidden)
            self.proj = nn.Dense(vocab, flatten=False)

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        h = self.l0(h)
        h = self.l1(h)
        return self.proj(h)


def layer_spec_fn(mp):
    """group2ctx, the mesh way: per-layer sharding rules.

    Layer-0 LSTM matrices are (4H, I)-shaped: shard the gate/output
    rows over 'mp'.  Layer-1 matrices shard the input columns instead,
    so the inter-layer activation exchange is the collective boundary
    (the reference's GPU1 -> GPU2 copy)."""
    from jax.sharding import PartitionSpec as P

    def spec(name, shape):
        # gluon names: twolayerlstm0_lstm0_l0_i2h_weight (first LSTM
        # block), ..._lstm1_l0_... (second block), ..._dense0_weight
        # (the projection) — the block index, not the intra-block
        # layer index, is the group2ctx "layer"
        if mp <= 1 or len(shape) != 2:
            return None
        if "_lstm0_" in name and "h2h" not in name \
                and shape[0] % mp == 0:
            return P("mp", None)      # layer 0: row-sharded
        if "_lstm1_" in name and "i2h" in name and shape[1] % mp == 0:
            return P(None, "mp")      # layer 1: column-sharded
        if "dense0_weight" in name and shape[0] % mp == 0:
            return P("mp", None)
        return None

    return spec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=12)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--hidden", type=int, default=32)
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel width (0 = devices//mp)")
    p.add_argument("--mp", type=int, default=2,
                   help="model-parallel width (layer sharding)")
    args = p.parse_args()

    import jax

    n_dev = len(jax.devices())
    mp = args.mp if args.mp > 0 and n_dev % args.mp == 0 else 1
    dp = args.dp or n_dev // mp
    mesh = parallel.make_mesh({"dp": dp, "mp": mp})
    print("devices=%d mesh=dp%d x mp%d" % (n_dev, dp, mp))

    mx.random.seed(7)
    net = TwoLayerLSTM(args.vocab, 16, args.hidden)
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    trainer = parallel.ShardedTrainer(
        net, lambda o, l: loss_fn(o.reshape((-1, args.vocab)),
                                  l.reshape((-1,))),
        mesh=mesh, optimizer="adam",
        optimizer_params={"learning_rate": 1e-2},
        param_spec_fn=layer_spec_fn(mp))

    # synthetic copy task: predict the previous token
    rng = np.random.RandomState(0)
    B = args.batch_size * dp
    data = rng.randint(1, args.vocab, (B, args.seq_len))
    x = data.astype(np.float32)
    y = np.concatenate([np.zeros((B, 1)), data[:, :-1]],
                       axis=1).astype(np.float32)

    xs, ys = trainer.shard_batch(nd.array(x), nd.array(y))
    first = last = None
    t0 = time.time()
    for step in range(args.steps):
        loss = trainer.step([xs], ys)
        lv = float(loss)
        first = lv if first is None else first
        last = lv
        if step % 5 == 0:
            print("step %3d loss %.4f" % (step, lv))
    print("%.1f steps/s" % (args.steps / (time.time() - t0)))

    # the demonstration must be real: verify the mp rules actually
    # placed layer shards (a renamed param would dead-code the spec fn
    # and this example would silently degrade to pure dp)
    n_mp = sum(1 for p, a in zip(trainer._params, trainer.param_arrays)
               if "mp" in str(getattr(a.sharding, "spec", "")))
    print("mp-sharded params: %d" % n_mp)
    converged = last < first * 0.5
    sharded = mp <= 1 or n_mp >= 3
    print("MODEL_PARALLEL_LSTM %s first=%.4f last=%.4f"
          % ("OK" if converged and sharded else "FAIL", first, last))
    return 0 if converged and sharded else 1


if __name__ == "__main__":
    sys.exit(main())
