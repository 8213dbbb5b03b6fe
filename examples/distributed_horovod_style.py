"""Horovod-style data-parallel training — counterpart of the
reference's example/distributed_training-horovod/resnet50_imagenet.py.

The Horovod recipe is: every worker holds a model replica, reads its
rank's shard of each batch, and allreduces gradients before the update.
TPU-native mapping: the mesh 'dp' axis IS the worker set; `shard_batch`
is the rank shard; the gradient allreduce is the psum XLA inserts from
the sharding annotations — fused into the same step program instead of
a separate NCCL phase.  Multi-host runs reuse the identical script:
`parallel.init_distributed()` joins the processes and the global mesh
spans them (tools/dryrun_multihost.py drills exactly that).

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/distributed_horovod_style.py --steps 25
Prints per-step losses, throughput, and "HOROVOD_STYLE OK ..." with the
allreduce-equivalence check (dp-sharded loss == single-device loss on
the same global batch).
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
from mxnet_tpu.gluon.model_zoo import vision


def build(args, mesh):
    mx.random.seed(11)
    net = vision.get_model(args.network, classes=args.num_classes)
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    return net, parallel.ShardedTrainer(
        net, lambda o, l: loss_fn(o, l), mesh=mesh, optimizer="sgd",
        optimizer_params={"learning_rate": args.lr, "momentum": 0.9})


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--network", default="resnet18_v1")
    p.add_argument("--num-classes", type=int, default=10)
    p.add_argument("--image-shape", default="3,32,32")
    p.add_argument("--batch-per-worker", type=int, default=4)
    p.add_argument("--steps", type=int, default=25)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--check-allreduce-equivalence", type=int, default=1)
    args = p.parse_args()

    import jax

    if os.environ.get("DMLC_ROLE"):      # launched under tools/launch.py
        parallel.init_distributed()
    n_dev = len(jax.devices())
    mesh = parallel.make_mesh({"dp": n_dev})
    shape = tuple(int(v) for v in args.image_shape.split(","))
    print("workers(dp)=%d global-batch=%d"
          % (n_dev, n_dev * args.batch_per_worker))

    net, trainer = build(args, mesh)
    rng = np.random.RandomState(3)
    B = n_dev * args.batch_per_worker
    x = rng.rand(B, *shape).astype(np.float32)
    y = rng.randint(0, args.num_classes, B).astype(np.float32)
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
    dt = time.time() - t0
    print("%.0f img/s over %d workers" % (B * args.steps / dt, n_dev))

    ok = last < first
    if args.check_allreduce_equivalence:
        # Horovod's defining property: the dp-sharded step equals a
        # single-device step on the concatenated batch.  Rebuild with
        # the same seed on a 1-device mesh and compare first losses.
        solo_mesh = parallel.make_mesh({"dp": 1}, jax.devices()[:1])
        _, solo = build(args, solo_mesh)
        sx, sy = solo.shard_batch(nd.array(x), nd.array(y))
        solo_first = float(solo.step([sx], sy))
        print("allreduce equivalence: dp first=%.6f solo first=%.6f"
              % (first, solo_first))
        ok = ok and abs(first - solo_first) < 5e-3
    print("HOROVOD_STYLE %s first=%.4f last=%.4f"
          % ("OK" if ok else "FAIL", first, last))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
