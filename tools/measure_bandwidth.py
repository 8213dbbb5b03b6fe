"""KVStore / collective bandwidth harness.

Counterpart of the reference's ``tools/bandwidth/measure.py`` (push+pull
bandwidth of a model's gradient set through the kvstore).  TPU-native
additions: the in-program path that actually carries gradients on this
stack — a jitted ``psum`` over the device mesh (ICI when real chips are
attached) — is measured alongside the host-side kvstore path and the
host<->device transfer ceiling.

Usage: python tools/measure_bandwidth.py [--network resnet50_v1]
       [--num-batches 5] [--kv-store local]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402


def model_grad_shapes(network, num_classes, image_shape):
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model(network, classes=num_classes)
    net.initialize(mx.init.Xavier())
    x = nd.array(np.zeros((1,) + image_shape, np.float32))
    net(x)  # materialize deferred shapes
    return [tuple(p.data().shape) for p in net.collect_params().values()
            if p.grad_req != "null"]


def measure_kvstore(shapes, kv_type, num_batches):
    kv = mx.kv.create(kv_type)
    grads = [nd.array(np.random.rand(*s).astype(np.float32))
             for s in shapes]
    outs = [nd.array(np.zeros(s, np.float32)) for s in shapes]
    for i, g in enumerate(grads):
        kv.init(i, nd.array(np.zeros(g.shape, np.float32)))
    total_bytes = sum(g.size for g in grads) * 4
    # warm round, drained before the timer starts (async dispatch)
    for i, (g, o) in enumerate(zip(grads, outs)):
        kv.push(i, [g])
        kv.pull(i, out=[o])
    for o in outs:
        o.asnumpy()
    t0 = time.time()
    for _ in range(num_batches):
        for i, (g, o) in enumerate(zip(grads, outs)):
            kv.push(i, [g])
            kv.pull(i, out=[o])
    for o in outs:
        o.asnumpy()
    dt = time.time() - t0
    return 2 * total_bytes * num_batches / dt / 1e9  # push+pull GB/s


def measure_psum(shapes, num_batches):
    """The real gradient-reduction path: one jitted psum over the mesh.
    On a single device the allreduce degenerates to an HBM read+write
    pass (an identity copy), which is the relevant ceiling there."""
    import jax
    import jax.numpy as jnp

    n_dev = jax.device_count()
    mesh_arrays = [jnp.asarray(np.random.rand(*s).astype(np.float32))
                   for s in shapes]

    @jax.jit
    def allreduce(tensors):
        # t + 1.0 can't be algebraically folded to an input alias (t*1.0
        # can), so single-device timing really pays the HBM read+write
        return [t + 1.0 for t in tensors]

    if n_dev > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("dp",))

        def ar(tensors):
            return [jax.lax.psum(t, "dp") for t in tensors]

        from mxnet_tpu.parallel import shard_map

        # args structure is the single list-typed parameter: the specs
        # pytree must be a 1-tuple wrapping the per-tensor list
        allreduce = jax.jit(
            shard_map(ar, mesh=mesh,
                      in_specs=([P()] * len(shapes),),
                      out_specs=[P()] * len(shapes)))
        mesh_arrays = [jax.device_put(a, NamedSharding(mesh, P()))
                       for a in mesh_arrays]

    total_bytes = sum(int(np.prod(s)) for s in shapes) * 4
    out = allreduce(mesh_arrays)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(num_batches):
        out = allreduce(mesh_arrays)
    jax.block_until_ready(out)
    dt = time.time() - t0
    return total_bytes * num_batches / dt / 1e9


def measure_transfer(shapes, num_batches):
    """Host<->device goodput; each timed upload ends in
    ``jax.block_until_ready``."""
    import jax

    hosts = [np.random.rand(*s).astype(np.float32) for s in shapes]
    total_bytes = sum(h.nbytes for h in hosts)
    devs = jax.block_until_ready([jax.device_put(h) for h in hosts])
    t0 = time.time()
    for _ in range(num_batches):
        devs = jax.block_until_ready([jax.device_put(h) for h in hosts])
    up = total_bytes * num_batches / (time.time() - t0) / 1e9
    t0 = time.time()
    for _ in range(num_batches):
        _ = [np.asarray(d) for d in devs]
    down = total_bytes * num_batches / (time.time() - t0) / 1e9
    return up, down


def main():
    p = argparse.ArgumentParser(description="kvstore/collective bandwidth")
    p.add_argument("--network", default="resnet50_v1")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-shape", default="3,224,224")
    p.add_argument("--kv-store", default="local")
    p.add_argument("--num-batches", type=int, default=5)
    args = p.parse_args()
    shape = tuple(int(x) for x in args.image_shape.split(","))

    shapes = model_grad_shapes(args.network, args.num_classes, shape)
    total_mb = sum(int(np.prod(s)) for s in shapes) * 4 / 1e6
    print("%s: %d gradient tensors, %.1f MB" % (args.network, len(shapes),
                                                total_mb))
    gbs = measure_psum(shapes, args.num_batches)
    print("in-program allreduce (psum): %.2f GB/s" % gbs)
    up, down = measure_transfer(shapes, args.num_batches)
    print("host->device %.2f GB/s, device->host %.2f GB/s" % (up, down))
    gbs = measure_kvstore(shapes, args.kv_store, args.num_batches)
    print("kvstore(%s) push+pull: %.2f GB/s" % (args.kv_store, gbs))


if __name__ == "__main__":
    main()
