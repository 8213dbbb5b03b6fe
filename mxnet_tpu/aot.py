"""Ahead-of-time compilation: serialized XLA executables + an artifact
store, so a restarting trainer or a freshly spawned serving replica
starts at warm-cache speed instead of paying the full trace+compile
cold start.

The deployable unit is the *compiled executable*, not the traced
program — the core lesson of the end-to-end compiler line (TVM, the
Julia->Cloud-TPU full-compilation work in PAPERS.md).  The runtime
already funnels every hot path through ``jax.jit`` (Executor fwd/bwd,
CachedOp, ShardedTrainer.step, serving.Predictor); this module wraps
those exact jitted callables:

* :class:`AOTFunction` — on the first call per input signature it runs
  ``jit(...).lower()`` (Python-trace cost only, no XLA compile), keys
  the lowering by a content hash (HLO text, arg shapes/dtypes/devices,
  jax+jaxlib+backend version, device topology, fusion/remat
  fingerprint), and asks the :class:`AOTStore`:

  - **hit**: the serialized executable is digest-verified,
    version-gated, deserialized, and dispatched — no XLA compile.
  - **miss**: ``lowered.compile()`` runs once and the executable is
    persisted (atomic temp+fsync+rename via ``checkpoint.atomic_write``)
    for every later process.
  - **anything wrong** (corrupt artifact, version skew, serialization
    unsupported, signature mismatch at dispatch): fall back to the
    plain jit path with a loud warning — a broken store can only cost
    cache misses, never wrong answers.

* :class:`AOTStore` — the on-disk artifact store: ``<key>.bin``
  (serialized executable payload) + ``<key>.json`` (schema, digest,
  environment fingerprint, signature, measured compile seconds).  The
  JSON is written last and is the commit point; loads verify the
  payload's SHA-256 against it, so a torn write is indistinguishable
  from a miss.  A ``manifest.jsonl`` records every executable signature
  the workload compiles, which lets ``tools/prewarm.py`` rebuild and
  compile everything ahead of rollout.

Enable with ``MXNET_AOT=1`` (store at ``MXNET_AOT_DIR``) or per call
site via ``aot=`` — threaded through bind/hybridize/ShardedTrainer/
Predictor exactly like ``fusion=`` and ``remat_policy=``.
"""
from __future__ import annotations

import datetime
import hashlib
import json
import os
import pickle
import sys
import threading
import time
import warnings

from . import config as _config
from . import events as _events
from . import telemetry as _telemetry
from . import tracing as _tracing

__all__ = ["AOTStore", "AOTFunction", "resolve_aot", "default_store",
           "environment_fingerprint", "executable_key", "unwrap",
           "set_store", "clear_store", "SCHEMA_VERSION"]

SCHEMA_VERSION = 1

_logger_warned = set()
_warn_lock = threading.Lock()


def _warn_once(tag, msg):
    """Loud once per (tag) — a broken store must be visible, but a
    thousand-step loop must not emit a thousand identical warnings."""
    with _warn_lock:
        if tag in _logger_warned:
            return
        _logger_warned.add(tag)
    warnings.warn(msg)


def _utcnow():
    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")


# ---------------------------------------------------------------------------
# keys and fingerprints
# ---------------------------------------------------------------------------


def environment_fingerprint():
    """Everything that can invalidate a serialized executable without
    changing the traced program: jax/jaxlib versions, backend, device
    kinds and count, process topology.  Rides in every entry's meta and
    gates loads — a mismatch is a miss, never a deserialization
    attempt."""
    import jax

    try:
        import jaxlib

        jaxlib_ver = jaxlib.__version__
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_ver = "?"
    devs = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib_ver,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "?",
        "device_count": len(devs),
        "process_count": jax.process_count(),
    }


def _leaf_sig(leaf):
    """(shape, dtype, weak_type, device) of one argument leaf.  Devices
    matter: serving pins one replica per device, and an executable
    compiled for device 1 cannot serve arrays committed to device 0."""
    shape = tuple(getattr(leaf, "shape", ()) or ())
    dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
    weak = bool(getattr(leaf, "weak_type", False))
    dev = ""
    devices = getattr(leaf, "devices", None)
    if callable(devices):
        try:
            devs = devices()
            if len(devs) == 1:
                dev = str(next(iter(devs)))
            else:
                dev = ",".join(sorted(str(d) for d in devs))
        except Exception:
            dev = ""
    return (shape, dtype, weak, dev)


def _execution_devices(leaves):
    """The device assignment jit gives a call with these argument
    leaves, in assignment order: the mesh of a NamedSharding argument,
    else the one device a committed argument pins, else the default
    device.  A stored executable must be loaded onto exactly these —
    left to itself, deserialization spreads a one-device program over
    every local device and the first dispatch fails."""
    import jax

    pinned = None
    for leaf in leaves:
        sharding = getattr(leaf, "sharding", None)
        mesh = getattr(sharding, "mesh", None)
        if mesh is not None:
            return list(mesh.devices.flat)
        if pinned is None and sharding is not None \
                and getattr(leaf, "committed", False):
            pinned = next(iter(sharding.device_set))
    return [pinned if pinned is not None else jax.devices()[0]]


def _signature(args, kwargs=None):
    """Canonical (per-leaf sigs, treedef) signature of a concrete
    argument tuple.  The treedef rides as the live PyTreeDef (hashable,
    deterministic repr) so it doubles as a dict key without
    stringifying per call."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    return tuple(_leaf_sig(x) for x in leaves), treedef


def executable_key(hlo_text, signature, fingerprint=None, extra=""):
    """Content hash naming one executable in the store.

    ``hlo_text`` is the lowered program (StableHLO) — it already
    reflects every graph-level decision (fusion rewrites, remat policy,
    shardings), so two processes tracing the same model at the same
    shapes produce the same key.  The environment fingerprint and the
    caller-supplied ``extra`` (fusion-plan / remat-policy tag) ride in
    the hash as belt-and-braces: anything that could make the artifact
    unusable or semantically different must change the key."""
    h = hashlib.sha256()
    h.update(hlo_text.encode() if isinstance(hlo_text, str) else hlo_text)
    h.update(repr(signature).encode())
    fp = fingerprint if fingerprint is not None else environment_fingerprint()
    h.update(json.dumps(fp, sort_keys=True).encode())
    h.update(str(extra).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the artifact store
# ---------------------------------------------------------------------------


class AOTStore:
    """Local directory of serialized executables, content-hash keyed.

    Writes are atomic (payload first, digest-bearing meta JSON last —
    the meta is the commit point); loads are digest-verified and
    version-gated, and any damage degrades to a compile, never to a
    wrong answer.
    """

    MANIFEST = "manifest.jsonl"

    def __init__(self, path):
        self.path = os.fspath(path)
        self._lock = threading.Lock()
        self._manifest_keys = None  # lazy cache of recorded keys

    def __repr__(self):
        return "AOTStore(%r)" % (self.path,)

    # -- paths -----------------------------------------------------------
    def _bin_path(self, key):
        return os.path.join(self.path, "%s.bin" % key)

    def _meta_path(self, key):
        return os.path.join(self.path, "%s.json" % key)

    def manifest_path(self):
        return os.path.join(self.path, self.MANIFEST)

    # -- save ------------------------------------------------------------
    def save(self, key, payload, meta):
        """Persist one executable: payload bytes then meta JSON, both
        atomic.  The meta carries the payload digest and is written
        last, so a reader never sees a meta without its verified
        payload."""
        from .checkpoint import atomic_write

        os.makedirs(self.path, exist_ok=True)
        digest = hashlib.sha256(payload).hexdigest()
        meta = dict(meta)
        meta.update({"schema": SCHEMA_VERSION, "key": key,
                     "digest": digest, "payload_bytes": len(payload),
                     "created": _utcnow()})
        atomic_write(self._bin_path(key), payload)
        atomic_write(self._meta_path(key),
                     json.dumps(meta, indent=1, sort_keys=True))
        return meta

    # -- load ------------------------------------------------------------
    def load_meta(self, key):
        """Parsed meta for ``key`` or None (missing/malformed — the
        malformed case warns: silent would hide bit-rot forever)."""
        try:
            with open(self._meta_path(key)) as f:
                meta = json.load(f)
        except OSError:
            return None
        except ValueError as e:
            _warn_once("meta:" + self.path + key,
                       "AOT store %s: malformed meta for %s (%s) — "
                       "treating as a miss (will recompile)"
                       % (self.path, key[:12], e))
            return None
        if not isinstance(meta, dict):
            return None
        return meta

    def load_payload(self, key, meta=None):
        """Digest-verified, version-gated payload bytes, or None.

        Every rejection reason is a *miss with a warning*, never an
        exception: the contract is that a damaged or stale store can
        only cost a recompile."""
        meta = meta if meta is not None else self.load_meta(key)
        if meta is None:
            return None
        if meta.get("schema") != SCHEMA_VERSION:
            _warn_once("schema:" + self.path + key,
                       "AOT store %s: entry %s has schema %r (supported "
                       "%d) — recompiling" % (self.path, key[:12],
                                              meta.get("schema"),
                                              SCHEMA_VERSION))
            return None
        fp = environment_fingerprint()
        stored = meta.get("fingerprint") or {}
        if stored != fp:
            # version/topology skew: a jax upgrade or a different mesh.
            # The key already folds the fingerprint in, so this only
            # triggers for hand-edited or cross-copied stores — still a
            # miss, still loud.
            _warn_once("fingerprint:" + self.path + key,
                       "AOT store %s: entry %s was built for %r, this "
                       "process is %r — recompiling"
                       % (self.path, key[:12], stored, fp))
            return None
        try:
            with open(self._bin_path(key), "rb") as f:
                payload = f.read()
        except OSError as e:
            _warn_once("payload:" + self.path + key,
                       "AOT store %s: meta for %s exists but payload is "
                       "unreadable (%s) — recompiling"
                       % (self.path, key[:12], e))
            return None
        digest = hashlib.sha256(payload).hexdigest()
        if digest != meta.get("digest"):
            _warn_once("digest:" + self.path + key,
                       "AOT store %s: entry %s failed its SHA-256 check "
                       "(corrupted or truncated artifact) — recompiling"
                       % (self.path, key[:12]))
            return None
        return payload

    # -- manifest --------------------------------------------------------
    def _read_manifest_keys(self):
        if self._manifest_keys is not None:
            return self._manifest_keys
        keys = set()
        try:
            with open(self.manifest_path()) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        keys.add(json.loads(line).get("key"))
                    except ValueError:
                        pass  # torn tail line: the next append is fine
        except OSError:
            pass
        self._manifest_keys = keys
        return keys

    def manifest_append(self, entry):
        """Record one executable signature (dedup by key).  A single
        O_APPEND write per line keeps concurrent recorders safe."""
        key = entry.get("key")
        with self._lock:
            if key in self._read_manifest_keys():
                return False
            os.makedirs(self.path, exist_ok=True)
            line = json.dumps(entry, sort_keys=True) + "\n"
            fd = os.open(self.manifest_path(),
                         os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
            try:
                os.write(fd, line.encode())
            finally:
                os.close(fd)
            self._manifest_keys.add(key)
        return True

    def manifest_entries(self):
        """Parsed manifest rows (malformed lines reported, not fatal).
        Returns (entries, problems)."""
        entries, problems = [], []
        try:
            with open(self.manifest_path()) as f:
                lines = f.readlines()
        except OSError:
            return [], []
        for i, line in enumerate(lines):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError as e:
                problems.append("manifest line %d: malformed (%s)"
                                % (i + 1, e))
                continue
            if not isinstance(row, dict) or "key" not in row:
                problems.append("manifest line %d: not an entry object"
                                % (i + 1))
                continue
            entries.append(row)
        return entries, problems

    # -- validation (tools/prewarm.py --check) ---------------------------
    def check(self, max_age_days=None, now=None):
        """Store integrity sweep: schema, digests, staleness vs the
        current environment.  Returns ``(problems, stale)`` —
        ``problems`` are malformed-store errors (nonzero exit in the
        CLI), ``stale`` are version-skewed or old entries (reported,
        they only cost recompiles)."""
        problems, stale = [], []
        if not os.path.isdir(self.path):
            return ["store directory %s does not exist" % self.path], []
        fp = environment_fingerprint()
        now = now if now is not None else datetime.datetime.now(
            datetime.timezone.utc)
        seen = 0
        for name in sorted(os.listdir(self.path)):
            if not name.endswith(".json") or name == self.MANIFEST:
                continue
            seen += 1
            key = name[:-5]
            try:
                with open(os.path.join(self.path, name)) as f:
                    meta = json.load(f)
            except (OSError, ValueError) as e:
                problems.append("%s: unreadable/malformed meta (%s)"
                                % (name, e))
                continue
            if not isinstance(meta, dict):
                problems.append("%s: meta is not an object" % name)
                continue
            if meta.get("schema") != SCHEMA_VERSION:
                problems.append("%s: schema %r != supported %d"
                                % (name, meta.get("schema"),
                                   SCHEMA_VERSION))
                continue
            for field in ("key", "digest", "label", "fingerprint"):
                if field not in meta:
                    problems.append("%s: missing field %r" % (name, field))
            if meta.get("key") not in (None, key):
                problems.append("%s: key field %r does not match file "
                                "name" % (name, meta.get("key")))
            bin_path = self._bin_path(key)
            if not os.path.exists(bin_path):
                problems.append("%s: payload %s.bin missing" % (name, key))
            else:
                try:
                    with open(bin_path, "rb") as f:
                        digest = hashlib.sha256(f.read()).hexdigest()
                except OSError as e:
                    problems.append("%s: payload unreadable (%s)"
                                    % (name, e))
                    digest = None
                if digest is not None and digest != meta.get("digest"):
                    problems.append("%s: payload SHA-256 mismatch "
                                    "(corrupted or truncated)" % name)
            stored_fp = meta.get("fingerprint") or {}
            if isinstance(stored_fp, dict) and stored_fp != fp:
                skew = {k: (stored_fp.get(k), fp.get(k))
                        for k in set(stored_fp) | set(fp)
                        if stored_fp.get(k) != fp.get(k)}
                stale.append("%s: built for a different environment %s"
                             % (name, skew))
            if max_age_days is not None and meta.get("created"):
                try:
                    created = datetime.datetime.fromisoformat(
                        meta["created"])
                    age = (now - created).total_seconds() / 86400.0
                    if age > float(max_age_days):
                        stale.append("%s: %.0f days old" % (name, age))
                except ValueError:
                    problems.append("%s: unparseable created timestamp %r"
                                    % (name, meta.get("created")))
        orphan_bins = [n for n in os.listdir(self.path)
                       if n.endswith(".bin")
                       and not os.path.exists(
                           os.path.join(self.path, n[:-4] + ".json"))]
        for n in sorted(orphan_bins):
            stale.append("%s: payload without meta (torn write leftover)"
                         % n)
        _, mproblems = self.manifest_entries()
        problems.extend(mproblems)
        return problems, stale

    def entries(self):
        """(key, meta) pairs for every committed entry."""
        if not os.path.isdir(self.path):
            return []
        out = []
        for name in sorted(os.listdir(self.path)):
            if name.endswith(".json") and name != self.MANIFEST:
                meta = self.load_meta(name[:-5])
                if meta is not None:
                    out.append((name[:-5], meta))
        return out


# ---------------------------------------------------------------------------
# resolution (the aot= contract, mirroring resolve_fusion)
# ---------------------------------------------------------------------------

_UNSET = object()
_override = _UNSET
_default_store_cache = {}


def default_store():
    """The process-default store at ``MXNET_AOT_DIR`` (one shared
    instance per path, so the manifest dedup cache is shared too)."""
    path = _config.get("MXNET_AOT_DIR")
    store = _default_store_cache.get(path)
    if store is None:
        store = _default_store_cache[path] = AOTStore(path)
    return store


def set_store(store):
    """Install a process-wide store override (``config.enable_aot``):
    a path, an :class:`AOTStore`, True (default dir), or False/None to
    force AOT off regardless of ``MXNET_AOT``."""
    global _override
    if isinstance(store, (str, os.PathLike)):
        store = AOTStore(store)
    elif store is True:
        store = default_store()
    elif store is False:
        store = None
    _override = store


def clear_store():
    """Back to the env default (``MXNET_AOT``/``MXNET_AOT_DIR``)."""
    global _override
    _override = _UNSET


def resolve_aot(spec):
    """``aot=`` argument -> :class:`AOTStore` or None (AOT off).

    Accepted: None (defer to the ``set_store`` override, else the
    ``MXNET_AOT`` env default), bool, a store directory path, or an
    :class:`AOTStore`."""
    if spec is None:
        if _override is not _UNSET:
            return _override
        return default_store() if _config.get("MXNET_AOT") else None
    if isinstance(spec, AOTStore):
        return spec
    if spec is False:
        return None
    if spec is True:
        return default_store()
    if isinstance(spec, (str, os.PathLike)):
        s = str(spec).strip().lower()
        if s in ("off", "none", "0", "false"):
            return None
        if s in ("on", "1", "true", "default"):
            return default_store()
        return AOTStore(spec)
    raise ValueError("aot= expects None/bool/path/AOTStore, got %r"
                     % (spec,))


# ---------------------------------------------------------------------------
# the jit wrapper
# ---------------------------------------------------------------------------


def unwrap(fn):
    """The raw ``jax.jit`` callable behind ``fn`` (identity for plain
    jits).  Trace-time consumers (``jax.eval_shape``, vjp-of-jit) must
    go through this: a serialized executable cannot be traced."""
    return fn.jit if isinstance(fn, AOTFunction) else fn


class AOTFunction:
    """Wrap a ``jax.jit`` callable with store-backed AOT dispatch.

    Per input signature the first call lowers the program (trace cost
    only), looks the content hash up in the store, and either
    deserializes the executable (hit) or compiles-and-persists it
    (miss).  Later calls with the same signature dispatch straight to
    the compiled executable.  Tracer arguments, signature churn, and
    every failure mode fall back to the plain jit path — the wrapper
    can only remove compiles, never change numerics.
    """

    def __init__(self, jit_fn, label, store, fingerprint_extra="",
                 manifest_kind=None, manifest_spec=None,
                 manifest_extra=None):
        self.jit = jit_fn
        self.label = label
        self.store = store
        self._extra = fingerprint_extra
        self._manifest_kind = manifest_kind
        self._manifest_spec = manifest_spec
        # extra manifest fields (e.g. the dtype-policy tag every
        # construction site records so tools/prewarm.py --check can
        # validate the precision recipe of each signature)
        self._manifest_extra = dict(manifest_extra or {})
        self._compiled = {}   # signature -> compiled executable
        self._lock = threading.Lock()

    def __repr__(self):
        return "AOTFunction(%s, store=%s)" % (self.label, self.store)

    # jit passthroughs used by cost analysis / trace-time consumers
    def lower(self, *args, **kwargs):
        return self.jit.lower(*args, **kwargs)

    def _sig_of(self, args, kwargs):
        return _signature(args, kwargs)

    def __call__(self, *args, **kwargs):
        import jax

        # one flatten serves both the tracer check and the dispatch
        # key: this runs on every hot-path call, so the per-leaf work
        # is kept to one pass and no string building beyond the leaf
        # device names
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
        sig_parts = []
        for leaf in leaves:
            if isinstance(leaf, jax.core.Tracer):
                # being traced into an outer program (vjp-of-jit,
                # eval_shape through the wrapper): only the raw jit
                # can inline
                return self.jit(*args, **kwargs)
            sig_parts.append(_leaf_sig(leaf))
        sig = (tuple(sig_parts), treedef)
        entry = self._compiled.get(sig)
        if entry is None:
            entry = self._acquire(sig, args, kwargs)
        if entry is self._FALLBACK:
            return self.jit(*args, **kwargs)
        try:
            return entry(*args, **kwargs)
        except Exception as e:
            # dispatch-time mismatch (device/layout drift, deleted
            # buffers from an aborted donated call): degrade this
            # signature to the jit path permanently
            _warn_once("dispatch:" + self.label,
                       "AOT %s: compiled-executable dispatch failed "
                       "(%s: %s); falling back to jit"
                       % (self.label, type(e).__name__, e))
            self._note_fallback("dispatch")
            with self._lock:
                self._compiled[sig] = self._FALLBACK
            return self.jit(*args, **kwargs)

    _FALLBACK = object()

    # -- acquisition -----------------------------------------------------
    def prewarm(self, *args, **kwargs):
        """Compile-or-load the executable for this signature WITHOUT
        executing it (safe with donated buffers).  Returns an info dict
        ``{status: hit|compiled|fallback, key, seconds,
        compile_seconds}`` — ``tools/prewarm.py`` aggregates these."""
        sig = self._sig_of(args, kwargs)
        t0 = time.perf_counter()
        entry = self._compiled.get(sig)
        if entry is not None:
            status = "fallback" if entry is self._FALLBACK else "warm"
            return {"label": self.label, "status": status,
                    "seconds": 0.0}
        info = {}
        self._acquire(sig, args, kwargs, info=info)
        info.setdefault("status", "fallback")
        info["label"] = self.label
        info["seconds"] = round(time.perf_counter() - t0, 3)
        return info

    def _acquire(self, sig, args, kwargs, info=None):
        """Lower, look up, load-or-compile, publish.  Any exception
        degrades to the jit path (counted + warned)."""
        import jax

        tel = _telemetry.enabled()
        try:
            t0 = time.perf_counter()
            lowered = self.jit.lower(*args, **kwargs)
            hlo = lowered.as_text()
            fp = environment_fingerprint()
            key = executable_key(hlo, sig, fingerprint=fp,
                                 extra=self._extra)
            if info is not None:
                info["key"] = key
            compiled = self._try_load(key, _execution_devices(
                jax.tree_util.tree_leaves((args, kwargs))))
            if compiled is not None:
                if tel:
                    _telemetry.AOT_CACHE_HITS.inc()
                    _telemetry.AOT_LOAD_SECONDS.observe(
                        time.perf_counter() - t0)
                if _events.enabled():
                    _events.emit("aot_load",
                                 dur_s=time.perf_counter() - t0,
                                 label=self.label, key=key[:16])
                if info is not None:
                    info["status"] = "hit"
                    meta = self.store.load_meta(key) or {}
                    info["compile_seconds"] = meta.get("compile_seconds")
            else:
                if tel:
                    _telemetry.AOT_CACHE_MISSES.inc()
                sp = _tracing.begin("aot:compile",
                                    args={"label": self.label,
                                          "key": key[:12]}) \
                    if _tracing.enabled() else None
                gp = sys.modules.get("mxnet_tpu.goodput")
                try:
                    t_c = time.perf_counter()
                    if gp is not None:
                        # this scope owns the goodput compile segment;
                        # the guard mutes the jax.monitoring bridge's
                        # backend_compile feed for the nested compile
                        with gp.compile_guard():
                            compiled = lowered.compile()
                    else:
                        compiled = lowered.compile()
                    compile_s = time.perf_counter() - t_c
                finally:
                    if sp is not None:
                        sp.end()
                if gp is not None:
                    gp.record_segment("compile", compile_s,
                                      label=self.label)
                if tel:
                    _telemetry.AOT_COMPILE_SECONDS.observe(compile_s)
                if _events.enabled():
                    _events.emit("aot_compile", dur_s=compile_s,
                                 label=self.label, key=key[:16])
                self._persist(key, compiled, sig, fp, compile_s)
                if info is not None:
                    info["status"] = "compiled"
                    info["compile_seconds"] = round(compile_s, 3)
            self._record_manifest(key, sig, fp)
            with self._lock:
                self._compiled[sig] = compiled
            return compiled
        except Exception as e:
            _warn_once("acquire:" + self.label,
                       "AOT %s: ahead-of-time path unavailable "
                       "(%s: %s); falling back to jit"
                       % (self.label, type(e).__name__, e))
            self._note_fallback("acquire")
            if _events.enabled():
                _events.emit("aot_compile", outcome="error",
                             error_kind="acquire", label=self.label,
                             detail="%s: %s" % (type(e).__name__, e))
            with self._lock:
                self._compiled[sig] = self._FALLBACK
            return self._FALLBACK

    def _try_load(self, key, devices):
        """Deserialize a stored executable onto ``devices`` (the
        assignment it was compiled for — they ride in the key), or None
        on any mismatch or damage (the store already warned)."""
        payload = self.store.load_payload(key)
        if payload is None:
            return None
        sp = _tracing.begin("aot:load", args={"label": self.label,
                                              "key": key[:12]}) \
            if _tracing.enabled() else None
        try:
            from jax.experimental import serialize_executable as _se

            ser, in_tree, out_tree = pickle.loads(payload)
            return _se.deserialize_and_load(ser, in_tree, out_tree,
                                            execution_devices=devices)
        except Exception as e:
            _warn_once("deserialize:" + key,
                       "AOT %s: stored executable %s failed to "
                       "deserialize (%s: %s) — recompiling"
                       % (self.label, key[:12], type(e).__name__, e))
            self._note_fallback("deserialize")
            return None
        finally:
            if sp is not None:
                sp.end()

    def _persist(self, key, compiled, sig, fp, compile_s):
        """Serialize + store the fresh executable (best-effort: a
        read-only store still serves this process from memory)."""
        try:
            from jax.experimental import serialize_executable as _se

            payload = pickle.dumps(_se.serialize(compiled))
            self.store.save(key, payload, {
                "label": self.label,
                "fingerprint": fp,
                "signature": [[list(s), d, w, dev]
                              for s, d, w, dev in sig[0]],
                "extra": self._extra,
                "compile_seconds": round(compile_s, 3),
            })
            if _telemetry.enabled():
                _telemetry.AOT_SAVES.inc()
        except Exception as e:
            _warn_once("persist:" + self.label,
                       "AOT %s: could not persist executable (%s: %s) — "
                       "this process keeps the compile, later processes "
                       "will recompile" % (self.label, type(e).__name__,
                                           e))
            self._note_fallback("persist")

    def _record_manifest(self, key, sig, fp):
        if self._manifest_kind is None or \
                not _config.get("MXNET_AOT_MANIFEST"):
            return
        try:
            entry = {
                "kind": self._manifest_kind,
                "spec": self._manifest_spec,
                "label": self.label,
                "key": key,
                "signature": [[list(s), d, w, dev]
                              for s, d, w, dev in sig[0]],
                "backend": fp.get("backend"),
                "created": _utcnow(),
            }
            entry.update(self._manifest_extra)
            entry.setdefault("dtype_policy", "f32")
            self.store.manifest_append(entry)
        except Exception as e:
            _warn_once("manifest:" + self.label,
                       "AOT %s: could not append signature manifest "
                       "(%s)" % (self.label, e))

    @staticmethod
    def _note_fallback(reason):
        if _telemetry.enabled():
            _telemetry.AOT_FALLBACKS.inc(reason=reason)


# ---------------------------------------------------------------------------
# /statusz subsystem view
# ---------------------------------------------------------------------------

def _statusz():
    """AOT store health for the introspection snapshot: hit/miss
    counters live in telemetry's base view; this adds the manifest's
    shape and staleness — row count, parse problems, age of the newest
    recorded signature (a stale manifest means prewarm has not run
    since the last deploy)."""
    store = resolve_aot(None)
    if store is None:
        return {"store": None, "enabled": False}
    out = {"store": store.path, "enabled": True}
    try:
        entries, problems = store.manifest_entries()
        out["manifest_rows"] = len(entries)
        out["manifest_problems"] = len(problems)
        newest = None
        for e in entries:
            c = e.get("created")
            if c and (newest is None or c > newest):
                newest = c
        out["manifest_newest"] = newest
        if newest:
            out["manifest_age_seconds"] = \
                _telemetry.iso_age_seconds(newest)
    except Exception as e:
        out["manifest_error"] = "%s: %s" % (type(e).__name__, e)
    return out


_telemetry.register_status_provider("aot", _statusz)
