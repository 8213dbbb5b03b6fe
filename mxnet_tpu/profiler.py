"""Profiler facade over the jax/XLA profiler.

Reference parity: src/profiler/ (chrome://tracing JSON dump, aggregate
stats) + python/mxnet/profiler.py:33,122,287 (set_config/start/stop/dumps)
+ scope classes (ProfileTask/Event/Frame/Domain).

TPU-native: jax.profiler emits a TensorBoard/XPlane trace (which includes
chrome-trace export) covering both host and TPU timelines — the same role
the reference's Profiler::DumpProfile JSON served.  Aggregate python-side
op stats are kept by this facade for `dumps()` parity.
"""
from __future__ import annotations

import bisect
import collections
import os
import re
import struct
import time

from . import telemetry as _telemetry

__all__ = ["set_config", "profiler_set_config", "start", "stop", "pause",
           "resume", "dump", "dumps", "set_state", "profiler_set_state",
           "device_table", "scope_path", "part_scope", "PART_SCOPES",
           "Domain", "Task", "Frame", "Event", "Counter", "Marker"]

_config = {"profile_all": False, "filename": "profile.json",
           "aggregate_stats": False}
_state = {"running": False, "dir": None}
_records = []
_op_stats = {}  # name -> [total_s, count, min_s, max_s]
# bounded timeline log feeding the chrome-trace dump(); entries are
# (name, start_s, dur_s) in perf_counter time.  At the cap the OLDEST
# event is evicted (the tail of a long run is what a post-mortem wants)
# and the drop is counted — silently freezing the timeline, as the old
# newest-dropped behavior did, made a saturated trace look complete.
_EVENT_CAP = 65536
_events = collections.deque(maxlen=_EVENT_CAP)
_dropped_events = 0
# per-compiled-program XLA cost analysis (flops / bytes accessed),
# attributed once per compile by the jit-path hooks
_xla_costs = {}


def set_config(**kwargs):
    """Parity: mx.profiler.set_config (profile_symbolic/profile_imperative/
    profile_memory/profile_api/aggregate_stats/filename)."""
    _config.update(kwargs)


def profiler_set_config(mode="symbolic", filename="profile.json"):
    _config["filename"] = filename


def set_state(state="stop", profile_process="worker"):
    if state == "run":
        start()
    else:
        stop()


profiler_set_state = set_state


def start(profile_process="worker"):
    import jax

    logdir = os.path.splitext(_config.get("filename", "profile.json"))[0] + "_trace"
    _state["dir"] = logdir
    try:
        jax.profiler.start_trace(logdir)
        _state["running"] = True
    except Exception:
        _state["running"] = False


def stop(profile_process="worker"):
    import jax

    if _state["running"]:
        try:
            jax.profiler.stop_trace()
        finally:
            _state["running"] = False


def pause(profile_process="worker"):
    stop(profile_process)


def resume(profile_process="worker"):
    start(profile_process)


def dump(finished=True, profile_process="worker"):
    """Write the chrome://tracing JSON to the configured ``filename``
    (reference Profiler::DumpProfile, src/profiler/profiler.h:256) and
    stop any running jax trace.

    The payload is the UNIFIED timeline (tracing.chrome_trace_payload):
    this facade's op events plus any hierarchical spans and per-device
    HBM counter samples from ``mxnet_tpu.tracing`` — one valid
    chrome/Perfetto file however the data was collected."""
    import json

    if _state["running"] and finished:
        stop()
    path = _config.get("filename", "profile.json")
    from . import tracing as _tracing

    payload = _tracing.chrome_trace_payload(include_profiler=True)
    payload["otherData"]["xla_costs"] = _xla_costs
    from .checkpoint import atomic_write

    atomic_write(path, json.dumps(payload))
    return path


def aggregate_enabled():
    """True when per-op aggregate stats collection is on."""
    return bool(_config.get("aggregate_stats"))


def record_op_time(name, dur_s, start_s=None):
    """Called by the dispatch layers per op/program when aggregation is
    enabled.  O(#op-names) running counters, like the reference's
    aggregate_stats.cc, plus a bounded timeline log for dump()."""
    st = _op_stats.get(name)
    if st is None:
        _op_stats[name] = [dur_s, 1, dur_s, dur_s]
    else:
        st[0] += dur_s
        st[1] += 1
        if dur_s < st[2]:
            st[2] = dur_s
        if dur_s > st[3]:
            st[3] = dur_s
    if start_s is None:
        start_s = time.perf_counter() - dur_s
    if _events.maxlen is not None and len(_events) == _events.maxlen:
        global _dropped_events

        _dropped_events += 1
        _telemetry.PROFILER_EVENTS_DROPPED.inc()
    _events.append((name, start_s, dur_s))


def timed_call(name, fn, args):
    """Run ``fn(*args)`` and, when aggregation is on, record the host's
    wall time of the call under ``name``: under asynchronous dispatch
    that is the dispatch, not the device's work, which
    :func:`device_table` reads from a trace.  The single helper keeps
    every jit-path hook (CachedOp, ShardedTrainer, Executor)
    behaviorally identical."""
    if not aggregate_enabled():
        return fn(*args)
    t0 = time.perf_counter()
    out = fn(*args)
    record_op_time(name, time.perf_counter() - t0, t0)
    return out


def record_xla_cost(name, analysis):
    """Attribute a compiled program's XLA cost analysis (flops, bytes
    accessed) — the jit-path analogue of the reference's per-op FLOP
    counters (storage_profiler.h role for the compiled path)."""
    if not isinstance(analysis, dict):
        return
    _xla_costs[name] = {
        "flops": float(analysis.get("flops", 0.0)),
        "bytes_accessed": float(analysis.get("bytes accessed",
                                             analysis.get("bytes_accessed",
                                                          0.0)))}


def device_memory_stats():
    """Per-device HBM counters from the XLA allocator (reference
    storage_profiler.h GpuDeviceStorageProfiler role).

    The schema is STABLE across backends: every local device gets an
    entry with at least ``bytes_in_use`` and ``peak_bytes_in_use``
    (zeros), plus an ``"unavailable"`` reason string on backends whose
    allocator exposes no ``memory_stats()`` (CPU on most jax builds) —
    dashboards and the flight recorder never have to special-case an
    empty dict."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return {}
    out = {}
    for d in devices:
        reason = None
        try:
            ms = d.memory_stats()
            if not ms:
                reason = ("memory_stats() returned %r on backend %r"
                          % (ms, getattr(d, "platform", "?")))
        except Exception as e:
            ms, reason = None, ("memory_stats() unsupported on backend "
                                "%r: %s" % (getattr(d, "platform", "?"), e))
        entry = {k: int(v) for k, v in (ms or {}).items()
                 if isinstance(v, (int, float))}
        entry.setdefault("bytes_in_use", 0)
        entry.setdefault("peak_bytes_in_use", 0)
        if reason is not None:
            entry["unavailable"] = reason
        out[str(d)] = entry
    return out


# -- device time by part of the program ------------------------------------
#
# A ``jax.named_scope`` on traced code reaches a trace as part of the
# ``tf_op`` stat on the operation's XEventMetadata, which
# ``jax.profiler.ProfileData`` drops; so the ``.xplane.pb`` is read here,
# by the protobuf wire format, the handful of fields that are wanted
# (tensorflow/tsl/profiler/protobuf/xplane.proto).

# the scopes the package's own traced code opens (docs/observability.md,
# "Where the device's time goes"); ``draft`` is an outer scope: the
# others nest inside it.  A Gluon block opens one named for itself.
PART_SCOPES = ("cache.gather", "cache.write", "sample", "embed", "head",
               "attn.proj", "attn.core", "attn.window", "kda.proj",
               "kda.scan", "gdn.proj", "gdn.scan", "ffn", "experts.route",
               "experts.ffn", "draft")

DeviceOp = collections.namedtuple("DeviceOp", (
    "program_id", "run_start_ns", "start_ns", "duration_ns", "scope",
    "source", "hlo_category", "bytes_accessed", "flops", "name", "device"))
# what a trace says of an operation once, whenever it ran
_OpMeta = collections.namedtuple("_OpMeta", (
    "program_id", "scope", "source", "hlo_category", "bytes_accessed",
    "flops", "name"))
_NO_META = _OpMeta(None, (), "", "", 0, 0, "")

_DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
# what JAX itself puts on the name stack: a jitted function's name, its
# control flow's parts
_JIT_WRAPPERS = ("jit", "pjit", "xla_computation")
_JAX_OWN = re.compile(
    r"^(pjit|closed_call|core_call|custom_jvp_call|custom_vjp_call"
    r"|custom_vjp_call_jaxpr|checkpoint|rematted_computation|while|body"
    r"|body_pred|cond|branch_\d+_fun|.*->.*|)$")


def scope_path(tf_op):
    """The named scopes an operation was traced under, outermost first,
    from its ``tf_op`` (``jit(chunk_fn)/jit(main)/experts.ffn/
    dot_general:``): the primitive's own name (the last component) goes,
    as do ``jit(...)``, ``pjit``, ``closed_call``, the parts of JAX's
    own control flow and an ``einsum``'s subscripts; ``jvp(...)``,
    ``transpose(...)``, ``vmap(...)`` and their like round a scope's
    name are taken off it, so that a block's forward and backward work
    read the same.  Where the compiler merged operations and kept their
    names side by side (``a/x;b/y``) the first is read.  ``()`` for an
    operation under no named scope."""
    path = []
    first = tf_op.partition(":")[0].partition(";")[0]
    for part in first.split("/")[:-1]:
        m = _WRAPPED.match(part)
        while m and m.group(1) not in _JIT_WRAPPERS:
            part = m.group(2)
            m = _WRAPPED.match(part)
        if not m and not _JAX_OWN.match(part):
            path.append(part)
    return tuple(path)


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of every field of one protobuf message: an
    int for a varint, a memoryview for the rest."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError("wire type %d in an XSpace" % wire)
        yield key >> 3, value


def _message(buf, **names):
    """One message's fields by name: ``name=number`` for a field taken
    once (the last wins), ``name=[number]`` for a repeated one."""
    once = {n: k for k, n in names.items() if not isinstance(n, list)}
    many = {n[0]: k for k, n in names.items() if isinstance(n, list)}
    out = {k: [] for k in many.values()}
    for number, value in _fields(buf):
        if number in once:
            out[once[number]] = value
        elif number in many:
            out[many[number]].append(value)
    return out


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _stats(stat_bufs, stat_names):
    """{stat name: value} of XStats: str, int or float; a ``ref_value``
    is the name of the stat metadata it points at."""
    out = {}
    for buf in stat_bufs:
        name = value = None
        for number, v in _fields(buf):
            if number == 1:
                name = stat_names.get(v)
            elif number == 2:
                value = struct.unpack("<d", v)[0]
            elif number in (3, 4):
                value = v
            elif number in (5, 6):
                value = _text(v)
            elif number == 7:
                value = stat_names.get(v, "")
        if name is not None:
            out[name] = value
    return out


def _map_values(entries):
    """The values of a protobuf map field's entries (key 1, value 2)."""
    return [_message(e, value=2).get("value", b"") for e in entries]


def _plane_ops(plane, device):
    """The DeviceOps of one device plane (an XPlane's fields)."""
    stat_names = {}
    for buf in _map_values(plane["stat_metadata"]):
        m = _message(buf, id=1, name=2)
        stat_names[m.get("id", 0)] = _text(m.get("name", b""))
    about = {}
    for buf in _map_values(plane["event_metadata"]):
        m = _message(buf, id=1, name=2, stats=[5])
        st = _stats(m["stats"], stat_names)
        tf_op = st.get("tf_op") or ""
        about[m.get("id", 0)] = _OpMeta(
            st.get("program_id"), scope_path(tf_op),
            st.get("source") or "", st.get("hlo_category") or "",
            int(st.get("bytes_accessed") or 0), int(st.get("flops") or 0),
            _text(m.get("name", b"")))

    # [(start_ns, duration_ns, metadata id)] of the two lines, by start
    events = {"XLA Modules": [], "XLA Ops": []}
    for buf in plane["lines"]:
        line = _message(buf, name=2, timestamp_ns=3, events=[4])
        out = events.get(_text(line.get("name", b"")))
        if out is None:
            continue
        t0 = line.get("timestamp_ns", 0)
        for ev in line["events"]:
            mid = offset_ps = duration_ps = 0
            for number, value in _fields(ev):
                if number == 1:
                    mid = value
                elif number == 2:
                    offset_ps = value
                elif number == 3:
                    duration_ps = value
            out.append((t0 + offset_ps / 1e3, duration_ps / 1e3, mid))
    runs, ops = sorted(events["XLA Modules"]), sorted(events["XLA Ops"])
    run_starts = [r[0] for r in runs]
    _lend_scopes(ops, about)
    # an operation that holds others (a ``while`` round its body's) is
    # given what is left of its time when theirs is taken off, so that
    # the durations of a run's operations add up to the time it was busy
    own = [dur for _s, dur, _m in ops]
    open_ = []                             # indices of enclosing ops
    for i, (start, dur, _m) in enumerate(ops):
        while open_ and ops[open_[-1]][0] + ops[open_[-1]][1] <= start:
            open_.pop()
        if open_:
            own[open_[-1]] -= dur
        open_.append(i)
    for (start, _dur, mid), self_ns in zip(ops, own):
        at = bisect.bisect_right(run_starts, start) - 1
        in_run = at >= 0 and start < runs[at][0] + runs[at][1]
        m = about.get(mid, _NO_META)
        yield DeviceOp(m.program_id, run_starts[at] if in_run else None,
                       start, max(self_ns, 0.0), m.scope, m.source,
                       m.hlo_category, m.bytes_accessed, m.flops, m.name,
                       device)


_OPERAND = re.compile(r"%[\w.\-]+")


def _lend_scopes(ops, about):
    """An operation the compiler added itself carries no ``tf_op``: the
    ``copy-done`` or ``slice-done`` in which the device waits for
    weights to reach fast memory, a ``copy`` that re-tiles an operand.
    It is lent the scope of the first operation to run that takes its
    result (an event's name is the whole HLO line, operands and all),
    and lends it on to what it takes itself (``copy-start``).  ``about``
    ({metadata id: _OpMeta}) is changed in place."""
    # every operation once, in the order of its first run, with the name
    # of its result and those of its operands
    order = {}
    for _start, _dur, mid in ops:
        if mid in about and mid not in order:
            result, _eq, rest = about[mid].name.partition(" = ")
            order[mid] = (result.strip(), _OPERAND.findall(rest))
    reader = {}                          # (program, name) -> scope

    def lend(mid, scope):
        for name in order[mid][1]:
            reader.setdefault((about[mid].program_id, name), scope)

    for mid in order:
        if about[mid].scope:
            lend(mid, about[mid].scope)
    for mid in reversed(order):          # a chain resolves from its end
        lent = reader.get((about[mid].program_id, order[mid][0]))
        if lent and not about[mid].scope:
            about[mid] = about[mid]._replace(scope=lent)
            lend(mid, lent)


def find_xplane(path):
    """``path`` itself if it is a file, else the newest ``*.xplane.pb``
    anywhere under the directory ``path``; None if there is none."""
    if os.path.isfile(path):
        return path
    found = [os.path.join(d, f) for d, _sub, files in os.walk(path)
             for f in files if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def device_table(path):
    """Every operation that ran on a device in the trace at ``path`` (an
    ``.xplane.pb``, or a directory ``jax.profiler`` wrote one under: the
    newest is taken), as :class:`DeviceOp` tuples: ``(program_id, start
    of the program's run, the operation's start_ns, duration_ns, scope
    path, source, hlo_category, bytes_accessed, flops, name, device)``,
    one for every event of the ``XLA Ops`` line of each
    ``/device:TPU:<n>`` plane, in the order of their starts.  ``scope``
    is :func:`scope_path` of the ``tf_op`` the compiler recorded for the
    operation: the ``jax.named_scope`` s it was traced under
    (:data:`PART_SCOPES` and the Gluon blocks' names), ``()`` under
    none; ``source`` the ``file:line`` that traced it;
    ``bytes_accessed`` and ``flops`` the compiler's own estimates for
    one execution.  ``duration_ns`` is the operation's own time: an
    operation that encloses others (a loop) is charged what its body's
    operations leave.

    **The attribution's known error**: the compiler fuses operations
    across scope boundaries, and a fusion carries the ``tf_op`` of ONE
    of its operations, its root's.  A fusion is therefore charged whole
    to its root's scope: a norm fused into the product that follows it
    counts as that product's.  Operations the compiler adds itself
    (copies, ``slice-start``/``-done`` that bring weights to fast
    memory) carry no ``tf_op``: each is lent the scope of the first
    operation that takes its result, and reads as unscoped only where
    none does.

    Returns a list, empty where the trace holds no device plane (a CPU
    run) or ``path`` no trace."""
    found = find_xplane(path) if path else None
    if found is None:
        return []
    with open(found, "rb") as f:
        space = memoryview(f.read())
    ops = []
    for number, buf in _fields(space):
        if number != 1:
            continue
        plane = _message(buf, name=2, lines=[3], event_metadata=[4],
                         stat_metadata=[5])
        m = _DEVICE_PLANE.match(_text(plane.get("name", b"")))
        if m:
            ops.extend(_plane_ops(plane, int(m.group(1))))
    return ops


def part_scope(path):
    """The innermost of :data:`PART_SCOPES` in a scope path, or None:
    the names of Gluon blocks between and below them are passed over."""
    for name in reversed(path):
        if name in PART_SCOPES:
            return name
    return None


def _device_scope_lines():
    """The "Device time by scope" table of the last ``start()`` /
    ``stop()`` session's trace, as lines."""
    ops = device_table(_state["dir"])
    head = "Device time by scope:"
    if not ops:
        return [head + " no device plane in the trace (%s): the table "
                "needs a run on an accelerator between start() and stop()"
                % _state["dir"]]
    device = max({op.device for op in ops}, key=lambda d: sum(
        op.duration_ns for op in ops if op.device == d))
    rows = {}
    for op in ops:
        if op.device != device:
            continue
        key = part_scope(op.scope) or (
            op.scope[-1] if op.scope else "(unscoped)")
        row = rows.setdefault(key, [0, 0.0, 0])
        row[0] += 1
        row[1] += op.duration_ns
        row[2] += op.bytes_accessed
    busy = sum(r[1] for r in rows.values()) or 1.0
    out = [head + " (device %d; a fusion counts whole under its root's "
           "scope)" % device,
           "%-40s %8s %12s %10s %12s %10s" % (
               "Scope", "Ops", "Total(ms)", "Busy(%)", "GB accessed",
               "GB/s")]
    for key, (n, ns, nbytes) in sorted(rows.items(),
                                       key=lambda kv: -kv[1][1]):
        out.append("%-40s %8d %12.4f %10.2f %12.4f %10.1f" % (
            key, n, ns / 1e6, 100.0 * ns / busy, nbytes / 1e9,
            nbytes / ns if ns else 0.0))
    return out


def dumps(reset=False):
    """Aggregate per-op statistics (reference aggregate_stats.cc table:
    name, count, total/min/max/avg ms: the host's wall time of each
    dispatch), the XLA cost table for compiled programs, after a
    ``start()`` / ``stop()`` session the device's time by named scope
    (:func:`device_table`), and device-memory counters."""
    agg = dict(_op_stats)
    for name, dur in _records:   # scope timers (Task/Event/Frame)
        tot, cnt, mn, mx = agg.get(name, (0.0, 0, float("inf"), 0.0))
        agg[name] = [tot + dur, cnt + 1, min(mn, dur), max(mx, dur)]
    out = ["Profile Statistics:",
           "%-32s %10s %12s %12s %12s %12s" % (
               "Name", "Calls", "Total(ms)", "Min(ms)", "Max(ms)",
               "Avg(ms)")]
    for name, (tot, cnt, mn, mx) in sorted(agg.items()):
        # count=0 placeholder rows (a registered name that never fired)
        # must render as zeros, not divide by zero
        avg = tot / cnt * 1e3 if cnt else 0.0
        mn = 0.0 if mn == float("inf") else mn
        out.append("%-32s %10d %12.4f %12.4f %12.4f %12.4f" % (
            name, cnt, tot * 1e3, mn * 1e3, mx * 1e3, avg))
    if _xla_costs:
        out.append("")
        out.append("XLA cost analysis (per compiled program):")
        out.append("%-40s %14s %16s" % ("Program", "GFLOPs", "MB accessed"))
        for name, c in sorted(_xla_costs.items()):
            out.append("%-40s %14.3f %16.3f" % (
                name, c["flops"] / 1e9, c["bytes_accessed"] / 1e6))
    if _state["dir"] and not _state["running"]:
        out.append("")
        out.extend(_device_scope_lines())
    mem = device_memory_stats()
    if mem:
        out.append("")
        out.append("Device memory:")
        for dev, st in mem.items():
            used = st.get("bytes_in_use", 0)
            peak = st.get("peak_bytes_in_use", 0)
            out.append("%-32s in_use %12d  peak %12d" % (dev, used, peak))
    if reset:
        global _dropped_events

        _records.clear()
        _op_stats.clear()
        _events.clear()
        _xla_costs.clear()
        # the drop count describes the cleared timeline; a fresh window
        # must not inherit it (the cumulative telemetry counter is the
        # process-lifetime view)
        _dropped_events = 0
    return "\n".join(out)


class Domain:
    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_event(self, name):
        return Event(name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)


class _Scope:
    def __init__(self, name):
        self.name = name
        self._t0 = None
        self._ann = None

    def start(self):
        import jax

        self._t0 = time.perf_counter()
        try:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:
            self._ann = None

    def stop(self):
        if self._t0 is not None:
            _records.append((self.name, time.perf_counter() - self._t0))
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()


class Task(_Scope):
    def __init__(self, domain, name):
        super().__init__(name)
        self.domain = domain


class Frame(_Scope):
    def __init__(self, domain, name):
        super().__init__(name)
        self.domain = domain


class Event(_Scope):
    pass


class Counter:
    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self.value = value or 0

    def set_value(self, value):
        self.value = value

    def increment(self, delta=1):
        self.value += delta

    def decrement(self, delta=1):
        self.value -= delta

    def __iadd__(self, v):
        self.value += v
        return self

    def __isub__(self, v):
        self.value -= v
        return self


class Marker:
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        _records.append((self.name, 0.0))
