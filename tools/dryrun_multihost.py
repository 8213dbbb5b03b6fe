"""Two-process multi-host dryrun (VERDICT r3 next-round #8).

Validates BOTH distributed paths over a DCN-style 2-host topology
without real multi-host hardware:

1. **Collective path** — 2 OS processes x 4 virtual CPU devices joined
   via ``jax.distributed`` (the ``parallel.mesh.init_distributed``
   bootstrap), one ``ShardedTrainer`` training step jitted over the
   global 8-device ``dp(hosts) x tp(local)`` mesh.  Each process feeds
   its own local batch shard (``make_array_from_process_local_data``),
   mirroring the reference's per-worker data loading; gradients cross
   the process boundary through compiler-inserted collectives — the
   DCN analogue of SURVEY §2.3's multi-machine dist_sync.
2. **Parameter-server path** — 1 server process + 2 worker processes
   over kvstore ``dist_sync`` (``kvstore_server.py``), one
   init/push/pull round verifying cross-worker aggregation.

Writes a MULTICHIP-style artifact:
    python tools/dryrun_multihost.py --json MULTIHOST_r04.json

Also hosts the offline sharded-checkpoint validator (no mesh, no jax —
pure file inspection; nonzero exit on coverage gaps / torn shards):
    python tools/dryrun_multihost.py --check-manifest /ckpt/dir [--step N]
"""
import argparse
import json
import os
import socket
import subprocess
import sys
import time

HERE = os.path.abspath(__file__)
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# worker body (runs in a fresh subprocess with JAX_PLATFORMS=cpu)
# ---------------------------------------------------------------------------


def collective_worker(rank, n_procs, dev_per_proc, port):
    os.environ["MXNET_DIST_COORDINATOR"] = "127.0.0.1:%d" % port
    os.environ["MXNET_DIST_NUM_PROCS"] = str(n_procs)
    os.environ["MXNET_DIST_PROC_ID"] = str(rank)

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, gluon, parallel
    from mxnet_tpu.gluon import nn
    from jax.sharding import PartitionSpec as P

    try:
        # env-driven bootstrap (retry-with-backoff inside); raises the
        # typed DistributedUnavailable on an unreachable coordinator
        up = parallel.bootstrap_distributed()
    except parallel.DistributedUnavailable as e:
        raise AssertionError("jax.distributed bootstrap failed: %s" % e)
    assert up, "jax.distributed bootstrap failed: not configured"
    assert jax.process_count() == n_procs
    devs = jax.devices()
    assert len(devs) == n_procs * dev_per_proc, \
        "global mesh sees %d devices" % len(devs)

    # default: dp spans the hosts (DCN), tp the intra-host devices
    # (ICI); --mesh overrides via the env relay (validated upstream)
    axes = parallel.parse_mesh(os.environ.get("MXTPU_MESH_SPEC")) or \
        {"dp": n_procs, "tp": dev_per_proc}
    mesh = parallel.make_mesh(axes, devs)
    local = [d for d in mesh.devices.flat if d.process_index == rank]
    print("MULTIHOST_MESH rank=%d axes=%s local_devices=%d" % (
        rank, json.dumps(parallel.mesh_shape(mesh), sort_keys=True),
        len(local)), flush=True)

    mx.random.seed(7)      # identical replicated params on every host
    np.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(8))
    net.initialize()

    tp_size = axes.get("tp", 0)

    def spec_fn(name, shape):
        if tp_size and name.endswith("weight") and len(shape) == 2 \
                and shape[0] % tp_size == 0:
            return P("tp", None)
        return None

    loss_fn = gluon.loss.L2Loss()
    trainer = parallel.ShardedTrainer(
        net, lambda o, l: loss_fn(o, l), mesh=mesh, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1}, param_spec_fn=spec_fn)

    # per-worker local batch shard (rank-dependent data, reference
    # per-worker iterator semantics)
    rng = np.random.RandomState(100 + rank)
    X = rng.rand(8, 16).astype(np.float32)
    Y = rng.rand(8, 8).astype(np.float32)
    xs, ys = trainer.shard_batch(nd.array(X), nd.array(Y))
    losses = []
    for _ in range(2):
        loss = trainer.step([xs], ys)
        jax.block_until_ready(loss)
        losses.append(float(np.asarray(loss)))
    assert all(np.isfinite(v) for v in losses), losses
    assert losses[1] < losses[0], "no training progress: %s" % losses
    # collective gather-back: tp-sharded params re-replicate across the
    # process boundary before the host fetch
    trainer.sync_to_net()
    for p in net.collect_params().values():
        assert np.isfinite(p.data().asnumpy()).all(), p.name
    print("MULTIHOST_LOSS rank=%d %r" % (rank, losses), flush=True)


def ps_server(port, n_workers):
    os.environ.update({
        "DMLC_ROLE": "server", "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port), "DMLC_NUM_WORKER": str(n_workers),
    })
    from mxnet_tpu.kvstore_server import run_server

    run_server()


def ps_worker(rank, port, n_workers):
    os.environ.update({
        "DMLC_ROLE": "worker", "DMLC_RANK": str(rank),
        "DMLC_PS_ROOT_URI": "127.0.0.1", "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(n_workers),
    })
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    kv = mx.kv.create("dist_sync")
    kv.init(3, nd.array(np.zeros((4, 4), np.float32)))
    kv.push(3, [nd.array(np.full((4, 4), float(rank + 1), np.float32))])
    out = nd.array(np.zeros((4, 4), np.float32))
    kv.pull(3, out=[out])
    total = float(out.asnumpy()[0, 0])
    expect = float(sum(range(1, n_workers + 1)))
    assert total == expect, (total, expect)
    print("MULTIHOST_PS rank=%d sum=%.1f" % (rank, total), flush=True)


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------


# mirror of parallel.mesh.MESH_AXES — local copy keeps the orchestrator
# free of the jax import (workers validate again through parse_mesh)
_MESH_AXES = ("dp", "fsdp", "pp", "ep", "sp", "mp", "tp")


def _parse_mesh_arg(spec):
    """Lightweight 'dp=2,tp=4' parse for the orchestrator (no jax)."""
    axes = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition("=")
        name = name.strip()
        if name not in _MESH_AXES or not size.strip().isdigit():
            raise SystemExit("bad --mesh entry %r (axis=size over %s)"
                             % (part, list(_MESH_AXES)))
        axes[name] = int(size)
    return axes


def _print_host_layout(axes, n_procs, dev_per_proc):
    """The resolved per-host view of --mesh: which axes span hosts (DCN)
    vs stay intra-host (ICI), and each rank's global device slice."""
    total = 1
    for v in axes.values():
        total *= v
    if total != n_procs * dev_per_proc:
        raise SystemExit(
            "--mesh %s needs %d devices; topology has %d procs x %d = %d"
            % (axes, total, n_procs, dev_per_proc,
               n_procs * dev_per_proc))
    order = [a for a in _MESH_AXES if a in axes]
    # device ids are laid out row-major in canonical axis order, hosts
    # own contiguous dev_per_proc blocks: an axis group touches ids
    # {i, i+stride, ..., i+(size-1)*stride}, so it stays inside one
    # host block only when its whole extent (stride * size) fits the
    # block — e.g. dp=4,tp=2 over 2x4 hosts has dp stride 2 but group
    # {0,2,4,6}, which crosses the host boundary
    stride = total
    spans = []
    for a in order:
        size = axes[a]
        extent = stride          # = stride(after) * size
        stride //= size
        spans.append((a, size, "hosts/DCN" if extent > dev_per_proc
                      and size > 1 else "local/ICI"))
    print("mesh %s over %d hosts x %d devices:"
          % (",".join("%s=%d" % (a, axes[a]) for a in order), n_procs,
             dev_per_proc), flush=True)
    for a, size, where in spans:
        print("  axis %-4s size %d  (%s)" % (a, size, where), flush=True)
    for r in range(n_procs):
        print("  rank %d: global devices [%d..%d]"
              % (r, r * dev_per_proc, (r + 1) * dev_per_proc - 1),
              flush=True)


def check_manifest(directory, step=None, prefix="ckpt"):
    """Offline sharded-checkpoint validation (manifest schema, shard
    presence/size, per-chunk digests, exact global coverage).  Returns
    a process exit code: 0 = restorable on any topology."""
    from mxnet_tpu.checkpoint import validate_sharded_checkpoint

    step, problems = validate_sharded_checkpoint(directory, step=step,
                                                 prefix=prefix)
    if step is None:
        print("check-manifest: %s" % problems[0], flush=True)
        return 2
    if problems:
        print("check-manifest: step %d has %d problem(s):"
              % (step, len(problems)), flush=True)
        for pr in problems:
            print("  - %s" % pr, flush=True)
        return 1
    print("check-manifest: step %d OK (restorable on any topology)"
          % step, flush=True)
    return 0


def run(n_procs=2, dev_per_proc=4, json_path=None, mesh=None):
    result = {"n_procs": n_procs, "dev_per_proc": dev_per_proc,
              "topology": "dp(%d hosts over DCN) x tp(%d local devices)"
                          % (n_procs, dev_per_proc)}
    if mesh:
        axes = _parse_mesh_arg(mesh)
        _print_host_layout(axes, n_procs, dev_per_proc)
        result["mesh"] = axes
        result["topology"] = mesh
        os.environ["MXTPU_MESH_SPEC"] = mesh  # relay to workers

    # --- 1. jax.distributed collective step ---
    port = _free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=%d"
                 % dev_per_proc)
    env["XLA_FLAGS"] = " ".join(flags)
    procs = [subprocess.Popen(
        [sys.executable, HERE, "--collective-worker", str(r),
         str(n_procs), str(dev_per_proc), str(port)],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for r in range(n_procs)]
    outs = []
    ok = True
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            out = "TIMEOUT"
        outs.append(out)
        ok = ok and p.returncode == 0
    result["collective_ok"] = ok
    losses = [ln for o in outs for ln in o.splitlines()
              if ln.startswith("MULTIHOST_LOSS")]
    result["collective_losses"] = losses
    if not ok:
        # raw worker output: callers (tests/test_multihost.py) classify
        # environmental bootstrap/timeout failures vs real regressions
        result["collective_outs"] = outs
    print("\n".join(losses) if ok else "COLLECTIVE FAILED:\n%s"
          % "\n".join(outs), flush=True)

    # --- 2. parameter-server dist_sync round ---
    port = _free_port()
    env_ps = dict(os.environ, JAX_PLATFORMS="cpu")
    sp = subprocess.Popen(
        [sys.executable, HERE, "--ps-server", str(port), str(n_procs)],
        env=env_ps, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    time.sleep(1.0)
    workers = [subprocess.Popen(
        [sys.executable, HERE, "--ps-worker", str(r), str(port),
         str(n_procs)],
        env=env_ps, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True) for r in range(n_procs)]
    ps_ok = True
    ps_out = []
    for p in workers:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            out = "TIMEOUT"
        ps_out.append(out)
        ps_ok = ps_ok and p.returncode == 0
    sp.kill()
    result["ps_ok"] = ps_ok
    result["ps_lines"] = [ln for o in ps_out for ln in o.splitlines()
                          if ln.startswith("MULTIHOST_PS")]
    print("\n".join(result["ps_lines"]) if ps_ok else "PS FAILED:\n%s"
          % "\n".join(ps_out), flush=True)

    result["ok"] = ok and ps_ok
    if json_path:
        with open(json_path, "w") as f:
            json.dump(result, f, indent=1)
        print("wrote", json_path)
    return result


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--collective-worker":
        collective_worker(*(int(v) for v in sys.argv[2:6]))
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "--ps-server":
        ps_server(int(sys.argv[2]), int(sys.argv[3]))
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "--ps-worker":
        ps_worker(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
        sys.exit(0)

    p = argparse.ArgumentParser()
    p.add_argument("--n-procs", type=int, default=2)
    p.add_argument("--dev-per-proc", type=int, default=4)
    p.add_argument("--json", default=None)
    p.add_argument("--mesh", default=None,
                   help="mesh spec for the collective drill, e.g. "
                        "'dp=2,tp=4' (product must equal n_procs x "
                        "dev_per_proc); prints the resolved per-host "
                        "layout before launching")
    p.add_argument("--check-manifest", metavar="DIR", default=None,
                   help="validate a committed sharded checkpoint "
                        "offline and exit (no mesh, no processes); "
                        "nonzero exit on gaps/torn shards")
    p.add_argument("--step", type=int, default=None,
                   help="with --check-manifest: validate this step "
                        "(default: newest committed)")
    p.add_argument("--prefix", default="ckpt",
                   help="with --check-manifest: checkpoint file prefix")
    a = p.parse_args()
    if a.check_manifest:
        sys.exit(check_manifest(a.check_manifest, step=a.step,
                                prefix=a.prefix))
    r = run(a.n_procs, a.dev_per_proc, a.json, mesh=a.mesh)
    sys.exit(0 if r["ok"] else 1)
