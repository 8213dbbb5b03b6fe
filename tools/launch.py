#!/usr/bin/env python
"""Distributed job launcher (counterpart of the reference's
tools/launch.py + dmlc-core tracker).

`--launcher local -n N` forks 1 parameter-server process + N worker
processes on this machine with the DMLC_* env contract the framework's
KVStoreDist / parallel.init_distributed read — the same pattern the
reference's CI uses for dist kvstore tests (SURVEY §4).

One process for each chip: this launcher never imports jax, the server
process is pinned to the host CPU (it only reduces host buffers), and
more than one local worker is refused unless ``JAX_PLATFORMS=cpu`` is
exported — each worker would otherwise initialise the accelerator, which
belongs to one process at a time (the second one fails or hangs).
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    parser = argparse.ArgumentParser(description="Launch a distributed job")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-s", "--num-servers", type=int, default=1,
                        help="(accepted for parity; the TCP PS uses 1)")
    parser.add_argument("--launcher", default="local",
                        choices=["local"],
                        help="multi-host launch is delegated to the cluster "
                             "scheduler (set DMLC_* env per host)")
    parser.add_argument("--sync-dst-dir", default=None)
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if args.num_workers > 1 and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        parser.error(
            "--launcher local would start %d workers on this host and "
            "each would initialise the accelerator, which belongs to one "
            "process at a time; export JAX_PLATFORMS=cpu to run them on "
            "the host CPU, or start one worker per host under the "
            "cluster scheduler" % args.num_workers)

    port = _free_port()
    base_env = dict(os.environ)
    base_env.update({
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NUM_WORKER": str(args.num_workers),
        "DMLC_NUM_SERVER": "1",
    })

    procs = []
    # server role
    server_env = dict(base_env, DMLC_ROLE="server", JAX_PLATFORMS="cpu")
    procs.append(subprocess.Popen(
        [sys.executable, "-c",
         "from mxnet_tpu.kvstore_server import run_server; run_server()"],
        env=server_env))
    # workers
    for rank in range(args.num_workers):
        env = dict(base_env, DMLC_ROLE="worker", DMLC_RANK=str(rank),
                   DMLC_WORKER_RANK=str(rank))
        procs.append(subprocess.Popen(args.command, env=env))

    rc = 0
    for p in procs[1:]:
        rc |= p.wait()
    procs[0].terminate()
    sys.exit(rc)


if __name__ == "__main__":
    main()
