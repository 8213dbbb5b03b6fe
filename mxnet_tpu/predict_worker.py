"""Worker side of the C predict ABI (cpp/mxtpu_predict.cc).

Reference counterpart: ``src/c_api/c_predict_api.cc`` /
``include/mxnet/c_predict_api.h`` — the deployment surface that lets a
model exported as symbol-json + params run from C without Python
linkage.  Design note: the reference implements the predictor in-process
because its executor is a C++ object; here the executor is jax/XLA
behind a Python surface, so the C library drives THIS worker over a
pipe (fork/exec) instead of embedding libpython — no interpreter/ABI
version coupling for the host app, crash isolation, and the IPC cost
(one round-trip per forward) is noise next to the XLA compute it
triggers.

One process for each chip: THIS worker is the chip's owner.  The C parent
(libmxtpu_runtime.so and the host app) never loads jax, so it can fork
the worker freely; one host app driving several workers on one chip is
not supported — the second worker's backend init fails or hangs.

Wire protocol (little-endian, over stdin/stdout):
    request  = u8 opcode | u64 payload_len | payload
    response = u8 status (0 ok, 1 error) | u64 payload_len | payload
opcodes:
    1 CREATE   payload: u64 json_len, json, u64 params_len, params
               (reference .params binary), u32 n_inputs, then per input
               u32 name_len, name, u32 ndim, u32 dims[ndim]
               reply: u32 n_outputs, then per output u32 ndim,
               u32 dims[ndim]
    2 SETINPUT payload: u32 name_len, name, f32 data[] (row-major,
               shape fixed at CREATE)
    3 FORWARD  no payload; reply empty
    4 GETOUT   payload: u32 index; reply f32 data[]
    5 RELOAD   payload: u64 params_len, params — hot-swap weights
    0 CLOSE    worker exits
"""
from __future__ import annotations

import os
import struct
import sys
import tempfile


def _read_exact(f, n):
    buf = b""
    while len(buf) < n:
        chunk = f.read(n - len(buf))
        if not chunk:
            raise EOFError("client closed the pipe")
        buf += chunk
    return buf


class _Server:
    def __init__(self):
        self.exe = None
        self.input_names = []
        self.input_shapes = {}
        self.arg_arrays = {}
        self.outputs = None

    # -- opcodes -----------------------------------------------------------

    def _load_params(self, params_bytes):
        from .ndarray import ndarray as nd_mod

        with tempfile.NamedTemporaryFile(suffix=".params",
                                         delete=False) as f:
            f.write(params_bytes)
            path = f.name
        try:
            # content-sniffing loader: reference binary OR npz
            loaded = nd_mod.load(path)
        finally:
            os.unlink(path)
        if not isinstance(loaded, dict):
            loaded = {"arg:%d" % i: a for i, a in enumerate(loaded)}
        arg, aux = {}, {}
        for name, arr in loaded.items():
            if name.startswith("arg:"):
                arg[name[4:]] = arr
            elif name.startswith("aux:"):
                aux[name[4:]] = arr
            else:
                arg[name] = arr
        return arg, aux

    def create(self, payload):
        import numpy as np

        import mxnet_tpu as mx
        from .ndarray.ndarray import array
        from .symbol import symbol as S

        off = 0
        (jlen,) = struct.unpack_from("<Q", payload, off)
        off += 8
        sym = S.load_json(payload[off:off + jlen].decode("utf-8"))
        off += jlen
        (plen,) = struct.unpack_from("<Q", payload, off)
        off += 8
        arg_p, aux_p = self._load_params(payload[off:off + plen])
        off += plen
        (n_in,) = struct.unpack_from("<I", payload, off)
        off += 4
        self.input_names, self.input_shapes = [], {}
        for _ in range(n_in):
            (nlen,) = struct.unpack_from("<I", payload, off)
            off += 4
            name = payload[off:off + nlen].decode("utf-8")
            off += nlen
            (ndim,) = struct.unpack_from("<I", payload, off)
            off += 4
            dims = struct.unpack_from("<%dI" % ndim, payload, off)
            off += 4 * ndim
            self.input_names.append(name)
            self.input_shapes[name] = tuple(int(d) for d in dims)

        args = dict(arg_p)
        for name in self.input_names:
            args[name] = array(np.zeros(self.input_shapes[name],
                                        np.float32))
        arg_names = set(sym.list_arguments())
        aux_names = set(sym.list_auxiliary_states())
        bind_args = {k: v for k, v in args.items() if k in arg_names}
        bind_aux = {k: v for k, v in aux_p.items() if k in aux_names}
        self.exe = sym.bind(mx.cpu() if os.environ.get(
            "MXTPU_PREDICT_CPU") else mx.context.current_context(),
            args=bind_args, aux_states=bind_aux or None)
        self.arg_arrays = bind_args
        self.aux_arrays = bind_aux
        self.sym = sym
        # probe output shapes with one forward
        outs = self.exe.forward(is_train=False)
        self.outputs = [o for o in outs]
        reply = struct.pack("<I", len(self.outputs))
        for o in self.outputs:
            reply += struct.pack("<I", len(o.shape))
            reply += struct.pack("<%dI" % len(o.shape),
                                 *[int(d) for d in o.shape])
        return reply

    def set_input(self, payload):
        import numpy as np

        from .ndarray.ndarray import array

        (nlen,) = struct.unpack_from("<I", payload, 0)
        name = payload[4:4 + nlen].decode("utf-8")
        shape = self.input_shapes[name]
        data = np.frombuffer(payload, np.float32,
                             offset=4 + nlen).reshape(shape)
        self.arg_arrays[name]._rebind(array(data.copy())._data)
        return b""

    def forward(self, payload):
        outs = self.exe.forward(is_train=False)
        self.outputs = [o for o in outs]
        return b""

    def get_output(self, payload):
        import numpy as np

        (idx,) = struct.unpack_from("<I", payload, 0)
        return np.ascontiguousarray(
            self.outputs[idx].asnumpy().astype(np.float32)).tobytes()

    def reload_params(self, payload):
        (plen,) = struct.unpack_from("<Q", payload, 0)
        arg_p, aux_p = self._load_params(payload[8:8 + plen])
        for k, v in arg_p.items():
            if k in self.arg_arrays and k not in self.input_names:
                self.arg_arrays[k]._rebind(v._data)
        # aux states (BatchNorm running stats) hot-swap with the weights
        for k, v in aux_p.items():
            if k in self.aux_arrays:
                self.aux_arrays[k]._rebind(v._data)
        return b""


def main():
    fin = sys.stdin.buffer
    # the wire owns fd 1.  Duplicate it for ourselves, then point fd 1
    # at stderr so NATIVE-level writes (XLA/plugin logging via printf)
    # cannot corrupt the length-prefixed protocol — reassigning
    # sys.stdout alone only catches python-level prints.
    fout = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    srv = _Server()
    ops = {1: srv.create, 2: srv.set_input, 3: srv.forward,
           4: srv.get_output, 5: srv.reload_params}
    while True:
        try:
            head = _read_exact(fin, 9)
        except EOFError:
            return
        opcode, plen = struct.unpack("<BQ", head)
        payload = _read_exact(fin, plen) if plen else b""
        if opcode == 0:
            return
        try:
            reply = ops[opcode](payload)
            fout.write(struct.pack("<BQ", 0, len(reply)) + reply)
        except Exception as e:  # error reply, keep serving
            msg = ("%s: %s" % (type(e).__name__, e)).encode("utf-8")
            fout.write(struct.pack("<BQ", 1, len(msg)) + msg)
        fout.flush()


if __name__ == "__main__":
    main()
