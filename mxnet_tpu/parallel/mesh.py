"""Device mesh construction + multi-host init.

Reference counterpart: src/kvstore/ device topology handling
(gpu_topology.h ComputeTreesFromRoot:1019 built reduction trees from
PCIe/NVLink scans) and ps-lite's DMLC_* bootstrap.  TPU-native: the
topology problem disappears — declare a jax.sharding.Mesh with named axes
(dp/tp/pp/sp/ep) and XLA lays collectives on ICI; multi-host joins via
jax.distributed.initialize from the same DMLC_*-style env the launcher
sets."""
from __future__ import annotations

import os

import numpy as np

from ..base import MXNetError as _MXNetError

__all__ = ["make_mesh", "init_distributed", "bootstrap_distributed",
           "distributed_env", "DistributedUnavailable",
           "UNAVAILABLE_SIGNATURES", "local_mesh", "MeshConfig",
           "shard_map", "parse_mesh", "resolve_mesh", "require_axes",
           "mesh_shape", "MESH_AXES", "DATA_AXES"]

# Canonical axis order, outermost first: dp neighbors sit farthest apart
# (cheapest axis to cross hosts / DCN), fsdp next (parameter shards want
# fast all-gathers but span more devices than tp), and mp/tp ride the
# innermost — fastest — ICI dimension, the standard layout recipe.
MESH_AXES = ("dp", "fsdp", "pp", "ep", "sp", "mp", "tp")

# Axes the *batch* dimension shards over.  fsdp is a data axis too: FSDP
# splits the batch like dp and additionally shards parameters/optimizer
# state along the same axis (ZeRO-3 discipline), which is what cuts the
# per-device state bytes.
DATA_AXES = ("dp", "fsdp")


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check_vma=None,
              **kwargs):
    """``jax.shard_map`` with ``check_vma`` passed only when given —
    the one spelling every shard_map in this package (and the tests)
    goes through."""
    import jax

    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


class MeshConfig:
    """Named axis sizes for a parallelism layout."""

    def __init__(self, dp=1, tp=1, pp=1, sp=1, ep=1, fsdp=1):
        self.dp, self.tp, self.pp, self.sp, self.ep = dp, tp, pp, sp, ep
        self.fsdp = fsdp

    def axes(self):
        return {k: v for k, v in
                (("dp", self.dp), ("fsdp", self.fsdp), ("tp", self.tp),
                 ("pp", self.pp), ("sp", self.sp), ("ep", self.ep))
                if v > 1} or {"dp": 1}


def parse_mesh(spec):
    """Parse a mesh spec string like ``"dp=2,fsdp=2,tp=2"`` into an axis
    dict (the ``mesh=`` / ``MXNET_MESH`` surface syntax).

    Also accepts a dict / :class:`MeshConfig` (returned as axes) and
    ``None``/``""`` (returns None).  Axis names are validated against
    :data:`MESH_AXES`; sizes must be positive ints.  ``"auto"`` maps the
    local device count onto a single ``dp`` axis."""
    if spec is None or spec == "":
        return None
    if isinstance(spec, MeshConfig):
        return spec.axes()
    if isinstance(spec, dict):
        axes = dict(spec)
    else:
        if not isinstance(spec, str):
            raise ValueError("mesh spec must be a 'dp=2,fsdp=2' string, "
                             "dict, or MeshConfig; got %r" % (spec,))
        if spec.strip() == "auto":
            import jax

            return {"dp": len(jax.devices())}
        axes = {}
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError("bad mesh spec %r: each entry must be "
                                 "axis=size (e.g. 'dp=2,fsdp=2')" % (spec,))
            name, _, size = part.partition("=")
            axes[name.strip()] = size.strip()
    out = {}
    for name, size in axes.items():
        if name not in MESH_AXES:
            raise ValueError("unknown mesh axis %r (supported: %s)"
                             % (name, list(MESH_AXES)))
        try:
            n = int(size)
        except (TypeError, ValueError):
            n = -1
        if n < 1:
            raise ValueError("mesh axis %s=%r must be a positive int"
                             % (name, size))
        out[name] = n
    return out or None


def resolve_mesh(mesh=None, devices=None):
    """Resolve the ``mesh=`` argument every front-end accepts.

    * a ``jax.sharding.Mesh`` — used as-is;
    * a spec string / dict / :class:`MeshConfig` — built via
      :func:`make_mesh`;
    * ``None`` — the ``MXNET_MESH`` env default ('' = no mesh, returns
      None: single-device semantics).
    """
    from jax.sharding import Mesh

    if isinstance(mesh, Mesh):
        return mesh
    if mesh is None:
        from .. import config as _config

        mesh = _config.get("MXNET_MESH") or None
        if mesh is None:
            return None
    axes = parse_mesh(mesh)
    if axes is None:
        return None
    return make_mesh(axes, devices)


def mesh_shape(mesh):
    """``{axis: size}`` of a Mesh (``{}`` for None) — the BENCH-JSON /
    checkpoint-manifest serialization of a topology."""
    if mesh is None:
        return {}
    return {str(a): int(s) for a, s in zip(mesh.axis_names,
                                           mesh.devices.shape)}


def require_axes(mesh, axes, who="this module"):
    """Loud validation that ``mesh`` carries every named axis.

    The parallel engines (moe/pipeline/ring/ulysses) declare the axes
    they consume through this instead of assuming a bare axis-0 device
    list; a missing axis fails here with the consuming module named,
    not deep inside shard_map placement."""
    if isinstance(axes, str):
        axes = (axes,)
    have = tuple(mesh.axis_names) if mesh is not None else ()
    missing = [a for a in axes if a not in have]
    if missing:
        raise ValueError(
            "%s needs mesh axis(es) %s but the mesh has %s — build the "
            "mesh with make_mesh({'%s': N, ...}) or mesh='%s=N'"
            % (who, missing, list(have) or "no axes", missing[0],
               missing[0]))
    return mesh


class DistributedUnavailable(_MXNetError):
    """jax.distributed bootstrap failed for an *environmental* reason —
    coordinator unreachable after retries, or the backend lacks
    multi-process collectives (CPU builds without a coordination
    service).  Tests and tools catch this for a typed skip instead of
    pattern-matching tracebacks.  The message embeds the underlying
    error so log-grep classifiers (test_multihost-style signatures)
    keep working."""


# error-text signatures that mark a backend/environment as incapable of
# multi-process collectives (shared with tests/test_multihost.py-style
# typed skips)
UNAVAILABLE_SIGNATURES = (
    "TIMEOUT", "bootstrap failed", "DEADLINE_EXCEEDED", "UNAVAILABLE",
    "failed to connect", "Barrier timed out", "coordination service",
    "aren't implemented on the CPU backend", "Unable to initialize backend",
)

_DIST_INITIALIZED = False


def distributed_env():
    """Resolve (coordinator, num_processes, process_id) from env.

    ``MXNET_DIST_COORDINATOR`` / ``MXNET_DIST_NUM_PROCS`` /
    ``MXNET_DIST_PROC_ID`` win; the legacy ps-lite contract
    (``DMLC_PS_ROOT_URI``+``MXTPU_COORD_PORT``, ``DMLC_NUM_WORKER``,
    ``DMLC_RANK``) and the ``MXTPU_*`` spellings remain as fallbacks so
    tools/launch.py keeps working.  Returns (None, 1, 0)-ish values
    when nothing is configured."""
    from .. import config as _config

    coordinator = (_config.get("MXNET_DIST_COORDINATOR")
                   or os.environ.get("MXTPU_COORDINATOR") or None)
    if coordinator is None and os.environ.get("DMLC_PS_ROOT_URI"):
        coordinator = "%s:%s" % (
            os.environ["DMLC_PS_ROOT_URI"],
            os.environ.get("MXTPU_COORD_PORT", "9191"))
    num_processes = (_config.get("MXNET_DIST_NUM_PROCS")
                     or int(os.environ.get(
                         "DMLC_NUM_WORKER",
                         os.environ.get("MXTPU_NUM_PROCS", "0")) or 0))
    process_id = _config.get("MXNET_DIST_PROC_ID")
    if process_id < 0:
        process_id = int(os.environ.get(
            "DMLC_RANK", os.environ.get("MXTPU_PROC_ID", "0")) or 0)
    return coordinator, int(num_processes), int(process_id)


def bootstrap_distributed(coordinator=None, num_processes=None,
                          process_id=None, retries=None, backoff=None,
                          logger=None):
    """``jax.distributed`` bootstrap with retry-with-backoff.

    Explicit args win over :func:`distributed_env`.  Returns ``False``
    when multi-process is simply not configured (no coordinator, or
    num_processes <= 1) and ``True`` once the distributed runtime is up
    (idempotent: a second call on an initialized runtime is a no-op).
    When configured but the coordinator stays unreachable after the
    retry budget — or the jax build cannot do multi-process — raises
    :class:`DistributedUnavailable` so callers get a *typed* skip
    instead of an arbitrary backend traceback.  Retry knobs default to
    ``MXNET_DIST_CONNECT_RETRIES`` / ``MXNET_DIST_CONNECT_BACKOFF``.
    """
    from .. import config as _config
    from ..checkpoint import retry as _retry

    env = distributed_env()
    coordinator = coordinator if coordinator is not None else env[0]
    num_processes = int(num_processes if num_processes is not None
                        else env[1])
    process_id = int(process_id if process_id is not None else env[2])
    if not coordinator or num_processes <= 1:
        return False
    global _DIST_INITIALIZED
    if _DIST_INITIALIZED:
        return True
    retries = (_config.get("MXNET_DIST_CONNECT_RETRIES")
               if retries is None else int(retries))
    backoff = (_config.get("MXNET_DIST_CONNECT_BACKOFF")
               if backoff is None else float(backoff))
    import jax

    def _connect():
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)

    try:
        _retry(_connect, retries=retries, backoff=backoff,
               exceptions=(Exception,), logger=logger)()
    except Exception as e:
        raise DistributedUnavailable(
            "jax.distributed bootstrap failed (coordinator=%s "
            "num_processes=%d process_id=%d, %d retries): %s"
            % (coordinator, num_processes, process_id, retries,
               e)) from e
    _DIST_INITIALIZED = True
    return True


def init_distributed(coordinator=None, num_processes=None, process_id=None):
    """Multi-host bootstrap (ps-lite scheduler parity). Reads the same
    env contract tools/launch.py sets (DMLC_PS_ROOT_URI/DMLC_RANK/...)
    plus the ``MXNET_DIST_COORDINATOR`` knob family; retry-with-backoff and the typed
    :class:`DistributedUnavailable` failure come from
    :func:`bootstrap_distributed`, which this wraps."""
    return bootstrap_distributed(coordinator=coordinator,
                                 num_processes=num_processes,
                                 process_id=process_id)


def make_mesh(axes=None, devices=None):
    """Build a Mesh from named axis sizes, e.g. {'dp': 4, 'tp': 2}.

    Axis order is fixed (dp, pp, ep, sp, mp, tp) so dp neighbors sit
    farthest apart and mp/tp ride the fastest ICI dimension — the
    standard layout recipe (shard the heaviest-traffic axis innermost).
    Unknown axis names raise."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if axes is None:
        axes = {"dp": len(devices)}
    order = [a for a in MESH_AXES if a in axes]
    # an unknown axis name must be loud, not silently dropped (r5: a
    # {'dp':4,'xx':2} request used to yield a dp-only mesh and the
    # caller's PartitionSpec('xx') failed far away at placement time)
    unknown = [a for a in axes if a not in MESH_AXES]
    if unknown:
        raise ValueError("unknown mesh axis names %s (supported: %s)"
                         % (unknown, list(MESH_AXES)))
    sizes = [axes[a] for a in order]
    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError("mesh needs %d devices, only %d available"
                         % (n, len(devices)))
    dev_array = np.asarray(devices[:n]).reshape(sizes)
    mesh = Mesh(dev_array, tuple(order))
    from .. import telemetry as _telemetry

    # topology gauge: one series per axis of the most recent mesh (a
    # no-op with telemetry off — same one-branch contract as every
    # other call site)
    for a, s in zip(order, sizes):
        _telemetry.MESH_DEVICES.set(int(s), axis=a)
    return mesh


def local_mesh(dp=None):
    """Mesh over all local devices with one 'dp' axis."""
    import jax

    devs = jax.devices()
    return make_mesh({"dp": dp or len(devs)}, devs)
