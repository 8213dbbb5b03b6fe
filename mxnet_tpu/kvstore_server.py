"""TCP parameter server: the ps-lite replacement.

Reference parity: 3rdparty/ps-lite (ZMQ PS: scheduler/server/worker roles
from DMLC_* env) + src/kvstore/kvstore_dist_server.h:155 (DataHandleEx:325,
sync aggregation ApplyUpdates:346 waiting for ps::NumWorkers() pushes,
server-side pickled-optimizer updates) + python/mxnet/kvstore_server.py.

Design: one server process (role=server, rank 0 by convention) listens on
DMLC_PS_ROOT_URI:DMLC_PS_ROOT_PORT.  Workers open one persistent socket
each.  Messages are length-prefixed pickles.  Sync mode: PUSH blocks until
NumWorkers pushes for that key are merged (the reference blocks at the
next engine sync instead — same observable ordering).  Async mode: each
push applies immediately (sync_mode_=false parity).  DCN-scale multi-host
TPU training should prefer the in-program collective path (mxnet_tpu/
parallel/); this server exists for kvstore='dist_*' API parity and for
CPU-host aggregation workloads (sparse embeddings).
"""
from __future__ import annotations

import json
import os
import pickle
import socket
import struct
import time
import threading

import numpy as np

__all__ = ["KVServer", "WorkerClient", "run_server", "_init_params"]


def _send_msg(sock, obj):
    payload = pickle.dumps(obj, protocol=4)
    sock.sendall(struct.pack("<Q", len(payload)) + payload)


def _recv_msg(sock):
    hdr = b""
    while len(hdr) < 8:
        chunk = sock.recv(8 - len(hdr))
        if not chunk:
            raise ConnectionError("socket closed")
        hdr += chunk
    (n,) = struct.unpack("<Q", hdr)
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            raise ConnectionError("socket closed")
        buf += chunk
    return pickle.loads(bytes(buf))


class KVServer:
    """The server role (KVStoreDistServer parity)."""

    def __init__(self, host, port, num_workers, sync_mode=True):
        self._store = {}
        self._push_buf = {}  # key -> (accum, count)
        self._num_workers = num_workers
        self._sync = sync_mode
        self._updater = None
        self._optimizer = None
        self._cv = threading.Condition()
        self._barrier_count = 0
        self._barrier_gen = 0
        # sync-round bookkeeping for ordering-divergence detection:
        # key -> (count of handler threads blocked on it, their target gen)
        self._waiting = {}
        self._divergence = None
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(num_workers + 2)
        self._done = threading.Event()
        # failure detection (reference kvstore_dist.h:121-126 node-death
        # handling): ranks whose connection dropped without shutdown
        self._dead = set()
        # server-side profiler (reference KVStoreServerProfilerCommand)
        self._prof_on = False
        self._prof_paused = False
        self._prof_stats = {}
        self._prof_file = "server_profile.json"

    def serve(self):
        threads = []
        for _ in range(self._num_workers):
            conn, _addr = self._sock.accept()
            t = threading.Thread(target=self._handle, args=(conn,), daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join()

    def _apply_update(self, key, agg):
        if self._optimizer is not None:
            # server-side optimizer (ApplyUpdates:346 parity): run the
            # pickled Optimizer via an Updater keyed by param key
            from .ndarray.ndarray import array as nd_array
            from . import optimizer as opt

            if self._updater is None:
                self._updater = opt.get_updater(self._optimizer)
            w = nd_array(self._store[key])
            g = nd_array(agg)
            self._updater(int(key) if key.isdigit() else key, g, w)
            self._store[key] = w.asnumpy()
        else:
            self._store[key] = self._store[key] + agg

    def _wait_error(self):
        if self._dead:
            return {"ok": False,
                    "error": "worker failure detected: dead rank(s) %s"
                             % sorted(self._dead)}
        if self._divergence:
            return {"ok": False, "error": self._divergence}
        return {"ok": False,
                "error": "timed out waiting for peers (no failure "
                         "detected; a worker may be stalled)"}

    def _push_one(self, key, value, async_req=False):
        """Apply/aggregate one pushed value; returns an error dict or
        None.  Sync mode blocks until every worker's contribution for
        this key has arrived (ApplyUpdates:346 parity)."""
        if not self._sync or async_req:
            # server-wide async mode, or an explicit per-push async
            # request from the worker
            with self._cv:
                self._apply_update(key, value)
            return None
        with self._cv:
            if self._dead:
                return self._wait_error()   # refuse rounds w/ dead peer
            acc, cnt, gen = self._push_buf.get(key, (0.0, 0, 0))
            acc = value if cnt == 0 else acc + value
            cnt += 1
            if cnt == self._num_workers:
                self._apply_update(key, acc)
                self._push_buf[key] = (0.0, 0, gen + 1)
                self._cv.notify_all()
            else:
                self._push_buf[key] = (acc, cnt, gen)
                target = gen + 1
                # ordering-divergence detection: each worker's handler
                # thread can block on at most one key, and the worker
                # that completes a round never blocks — so if every
                # worker is genuinely blocked (its target generation not
                # yet reached; a satisfied waiter that hasn't been
                # rescheduled doesn't count) across more than one
                # distinct key, no round can ever complete.  Fail fast
                # instead of waiting out the timeout.
                cnt_w, _ = self._waiting.get(key, (0, target))
                self._waiting[key] = (cnt_w + 1, target)
                blocked = [k for k, (c, t) in self._waiting.items()
                           if c > 0 and self._push_buf.get(
                               k, (0.0, 0, 0))[2] < t]
                if (sum(self._waiting[k][0] for k in blocked)
                        >= self._num_workers
                        and len(blocked) > 1
                        and self._divergence is None):
                    self._divergence = (
                        "sync push ordering divergence: all %d workers "
                        "blocked across keys %s — every worker must push "
                        "the same key sequence in sync mode"
                        % (self._num_workers, sorted(blocked)))
                    self._cv.notify_all()
                self._cv.wait_for(
                    lambda: self._push_buf[key][2] >= target
                    or self._dead or self._divergence, timeout=600)
                c2w, t2w = self._waiting[key]
                self._waiting[key] = (c2w - 1, t2w)
                if self._push_buf[key][2] < target:
                    # failed round: withdraw this worker's contribution
                    # so a retry can never double-count it, then fail
                    a2, c2, g2 = self._push_buf[key]
                    if g2 < target and c2 > 0:
                        self._push_buf[key] = (
                            (0.0, 0, g2) if c2 == 1
                            else (a2 - value, c2 - 1, g2))
                    err = self._wait_error()
                    # the divergence round is over once its last waiter
                    # has withdrawn; later rounds start clean
                    if not any(c for c, _ in self._waiting.values()):
                        self._divergence = None
                    return err
        return None

    @staticmethod
    def _flag(body, default=False):
        """Accept '1'/'0' and the profiler's 'run'/'stop' strings."""
        s = str(body or "").strip().lower()
        if s in ("1", "run", "true", "on"):
            return True
        if s in ("0", "stop", "false", "off"):
            return False
        return default

    def _handle_command(self, head, body):
        """Worker->server control channel.  Profiler heads mirror the
        reference KVStoreServerProfilerCommand enum (kvstore.h:49):
        set_config / state / pause / dump operate a server-side op-stat
        collector (per-op counts + wall time), dumped as JSON.  Errors
        must come back as {'ok': False} — an escaping exception would
        kill this handler thread and mark the worker's rank dead."""
        try:
            if head == "profiler_set_config":
                with self._cv:
                    self._prof_file = str(body or "server_profile.json")
                    self._prof_stats = {}
                return {"ok": True}
            if head == "profiler_state":
                with self._cv:
                    self._prof_on = self._flag(body)
                    self._prof_paused = False
                return {"ok": True}
            if head == "profiler_pause":
                with self._cv:
                    pause = self._flag(body, default=True)
                    if pause:
                        self._prof_paused = self._prof_on
                        self._prof_on = False
                    elif self._prof_paused:
                        # resume restores the pre-pause state; it never
                        # force-enables a profiler that was off
                        self._prof_on = True
                        self._prof_paused = False
                return {"ok": True}
            if head == "profiler_dump":
                with self._cv:
                    stats = dict(self._prof_stats)
                    path = self._prof_file
                from .checkpoint import atomic_write

                atomic_write(path, json.dumps(stats))
                return {"ok": True, "path": path}
            return {"ok": True}   # unknown heads accepted, like the ref
        except Exception as e:
            return {"ok": False, "error": "server command %r failed: %s"
                                          % (head, e)}

    def _prof_record(self, op, seconds):
        if self._prof_on:
            with self._cv:
                cnt, total = self._prof_stats.get(op, (0, 0.0))
                self._prof_stats[op] = (cnt + 1, total + seconds)

    def _handle(self, conn):
        rank = None
        clean_exit = False
        try:
            while not self._done.is_set():
                msg = _recv_msg(conn)
                op = msg["op"]
                if op == "hello":
                    rank = msg.get("rank")
                    _send_msg(conn, {"ok": True})
                elif op == "health":
                    with self._cv:
                        dead = sorted(self._dead)
                    _send_msg(conn, {"ok": True, "dead": dead})
                elif op == "init":
                    with self._cv:
                        self._store.setdefault(msg["key"], msg["value"])
                    _send_msg(conn, {"ok": True})
                elif op == "push":
                    t0 = time.monotonic()
                    err = self._push_one(msg["key"], msg["value"],
                                         msg.get("async"))
                    self._prof_record("push", time.monotonic() - t0)
                    _send_msg(conn, err or {"ok": True})
                elif op == "push_batch":
                    # one RTT for a whole step's gradients: keys are
                    # aggregated in order, so every worker's handler
                    # thread walks the same sequence of sync rounds
                    t0 = time.monotonic()
                    err = None
                    for key, value in msg["items"]:
                        err = self._push_one(key, value, msg.get("async"))
                        if err:
                            break
                    self._prof_record("push_batch",
                                      time.monotonic() - t0)
                    _send_msg(conn, err or {"ok": True})
                elif op == "pull":
                    t0 = time.monotonic()
                    with self._cv:
                        val = self._store[msg["key"]]
                    self._prof_record("pull", time.monotonic() - t0)
                    _send_msg(conn, {"ok": True, "value": val})
                elif op == "pull_batch":
                    t0 = time.monotonic()
                    with self._cv:
                        vals = [self._store[k] for k in msg["keys"]]
                    self._prof_record("pull_batch",
                                      time.monotonic() - t0)
                    _send_msg(conn, {"ok": True, "values": vals})
                elif op == "set_optimizer":
                    self._optimizer = pickle.loads(msg["value"])
                    self._updater = None
                    _send_msg(conn, {"ok": True})
                elif op == "barrier":
                    with self._cv:
                        if self._dead:
                            _send_msg(conn, self._wait_error())
                            continue
                        gen = self._barrier_gen
                        self._barrier_count += 1
                        if self._barrier_count == self._num_workers:
                            self._barrier_count = 0
                            self._barrier_gen += 1
                            self._cv.notify_all()
                        else:
                            self._cv.wait_for(
                                lambda: self._barrier_gen > gen
                                or self._dead, timeout=600)
                            if self._barrier_gen <= gen:
                                self._barrier_count = max(
                                    0, self._barrier_count - 1)
                                _send_msg(conn, self._wait_error())
                                continue
                    _send_msg(conn, {"ok": True})
                elif op == "command":
                    _send_msg(conn, self._handle_command(
                        msg.get("head"), msg.get("body")))
                elif op == "shutdown":
                    _send_msg(conn, {"ok": True})
                    self._done.set()
                    clean_exit = True
                    break
        except (ConnectionError, OSError, EOFError):
            pass
        finally:
            if not clean_exit and not self._done.is_set():
                with self._cv:
                    self._dead.add(-1 if rank is None else int(rank))
                    # discard the broken round's partial state: with a
                    # dead peer no collective can complete, and a retry
                    # must not double-count the survivors' contributions
                    self._push_buf = {k: (0.0, 0, gen)
                                      for k, (_a, _c, gen)
                                      in self._push_buf.items()}
                    self._barrier_count = 0
                    self._cv.notify_all()
            conn.close()


class WorkerClient:
    """Worker-side connection (ps::KVWorker parity)."""

    def __init__(self, host, port, rank, num_workers):
        self.rank = rank
        self.num_workers = num_workers
        from .checkpoint import retry

        # the launcher starts the server and the workers together: a
        # worker that comes up first retries until the server listens
        connect = retry(socket.create_connection, retries=8, backoff=0.25,
                        exceptions=(ConnectionRefusedError,))
        self._sock = connect((host, port), timeout=600)
        self._lock = threading.Lock()
        self._rpc(op="hello", rank=rank)

    @classmethod
    def from_env(cls):
        from . import config as _config

        host = os.environ["DMLC_PS_ROOT_URI"]
        port = _config.get("DMLC_PS_ROOT_PORT")
        rank = int(os.environ.get("DMLC_WORKER_RANK",
                                  os.environ.get("DMLC_RANK", "0")))
        num_workers = _config.get("DMLC_NUM_WORKER")
        return cls(host, port, rank, num_workers)

    def _rpc(self, **msg):
        from .base import MXNetError

        with self._lock:
            _send_msg(self._sock, msg)
            resp = _recv_msg(self._sock)
        if not resp.get("ok", True):
            # a peer died mid-collective (reference node-failure surface)
            raise MXNetError(resp.get("error", "kvstore server error"))
        return resp

    def health(self):
        """Dead ranks the server has detected so far."""
        return self._rpc(op="health").get("dead", [])

    def init(self, key, value):
        self._rpc(op="init", key=key, value=np.asarray(value))

    def push(self, key, value, sync=True):
        """sync=False applies this push immediately server-side instead
        of waiting for the other workers' contributions."""
        msg = {"op": "push", "key": key, "value": np.asarray(value)}
        if not sync:
            msg["async"] = True
        self._rpc(**msg)

    def pull(self, key):
        return self._rpc(op="pull", key=key)["value"]

    def push_batch(self, items, sync=True):
        """One RTT for many (key, value) pushes — a full training step's
        gradients travel in a single message."""
        msg = {"op": "push_batch",
               "items": [(k, np.asarray(v)) for k, v in items]}
        if not sync:
            msg["async"] = True
        self._rpc(**msg)

    def pull_batch(self, keys):
        return self._rpc(op="pull_batch", keys=list(keys))["values"]

    def set_optimizer(self, pickled):
        self._rpc(op="set_optimizer", value=pickled)

    def barrier(self):
        self._rpc(op="barrier")

    def command(self, head, body):
        self._rpc(op="command", head=head, body=body)

    def shutdown(self):
        try:
            self._rpc(op="shutdown")
        except ConnectionError:
            pass


def _init_params():
    from . import config as _config

    return (os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1"),
            _config.get("DMLC_PS_ROOT_PORT"),
            _config.get("DMLC_NUM_WORKER"))


def run_server(sync_mode=None):
    """Entry for role=server processes (parity: kvstore_server.py:64-73 /
    MXKVStoreRunServer)."""
    host, port, num_workers = _init_params()
    if sync_mode is None:
        sync_mode = os.environ.get("MXTPU_PS_ASYNC", "0") != "1"
    server = KVServer("0.0.0.0", port, num_workers, sync_mode=sync_mode)
    server.serve()


if __name__ == "__main__":  # pragma: no cover
    run_server()
