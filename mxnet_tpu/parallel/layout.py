"""Spec-layout registry: per-parameter PartitionSpec rules by name.

The reference sharded parameters by hashing names across ps-lite
servers (src/kvstore/kvstore_dist.h) — placement was an implementation
detail the user never saw.  The GSPMD-era equivalent (SNIPPETS [2]/[3]:
per-parameter PartitionSpec rule tables keyed by name) makes placement a
*declared, inspectable* artifact: a :class:`Layout` is an ordered list
of :class:`SpecRule` (regex over the gluon parameter name + an optional
rank filter -> PartitionSpec), resolved once against a model's
parameters at bind time and cached.

Canonical built-ins:

* ``data_parallel`` — every parameter replicated; the batch shards over
  the data axes (dp, and fsdp when present).  The PR-1..8 default.
* ``fsdp``          — every parameter and optimizer-state leaf sharded
  along ``fsdp`` on dim 0 (vectors along their only dim): ZeRO-3
  state partitioning.  XLA regathers parameters on use.
* ``fsdp_tp``       — fsdp plus Megatron-style tensor parallelism over
  ``tp`` for transformer projections: qkv/up projections
  column-parallel (dim 0 = out features on the mxnet (out, in) weight
  convention), out/down projections row-parallel, embeddings and the
  LM head split over both axes.

Resolution is STRICT: a parameter no rule matches raises (layouts end
with an explicit catch-all where replication is intended — silent
replication is how a "sharded" run quietly stops fitting in HBM).  Two
degradations are legal but *recorded* in the resolution report, never
silent: a spec axis the mesh does not carry is dropped (layouts name
logical axes; the mesh decides which are physical), and a dimension not
divisible by its axis size falls back to unsharded for that dim.
"""
from __future__ import annotations

import re
import threading

from ..base import MXNetError

__all__ = ["SpecRule", "Layout", "LayoutResolution", "register_layout",
           "get_layout", "list_layouts", "resolve_layout",
           "default_layout_for"]


class SpecRule:
    """One ordered rule: ``pattern`` (regex, ``re.search`` over the full
    parameter name) + optional rank filter -> partition-spec axes.

    ``spec`` is a tuple of mesh-axis entries per dimension — each entry
    an axis name, a tuple of axis names (that dim sharded over both),
    or None (unsharded).  Shorter than the parameter rank is fine
    (trailing dims unsharded, the jax PartitionSpec convention).

    ``rank`` pins an exact ndim; ``min_rank`` a lower bound — rules for
    matrices (`min_rank=2`) vs vectors (`rank=1`) keep one name pattern
    from accidentally sharding a bias like a weight.
    """

    def __init__(self, name, pattern, spec, rank=None, min_rank=None):
        self.name = name
        self.pattern = pattern
        self._re = re.compile(pattern)
        self.spec = tuple(spec)
        self.rank = rank
        self.min_rank = min_rank

    def matches(self, param_name, shape):
        if self.rank is not None and len(shape) != self.rank:
            return False
        if self.min_rank is not None and len(shape) < self.min_rank:
            return False
        return self._re.search(param_name) is not None

    def __repr__(self):
        return "SpecRule(%r, %r -> %r)" % (self.name, self.pattern,
                                           self.spec)


class LayoutResolution:
    """The bind-time product of ``Layout.resolve``: per-parameter
    PartitionSpecs plus the audit trail (which rule fired, which axes
    were dropped for a missing mesh axis, which dims fell back for
    divisibility)."""

    def __init__(self, layout_name, mesh_axes):
        self.layout_name = layout_name
        self.mesh_axes = dict(mesh_axes)
        self.specs = {}        # param name -> PartitionSpec
        self.rules = {}        # param name -> rule name
        self.dropped_axes = {}  # param name -> [axis names not in mesh]
        self.fallbacks = {}    # param name -> [dims degraded to None]

    def spec(self, name):
        return self.specs[name]

    def rule(self, name):
        return self.rules[name]

    def spec_strings(self):
        """``{param: "P('fsdp', 'tp')"}`` — the checkpoint-manifest /
        debugging serialization."""
        return {k: str(v) for k, v in self.specs.items()}

    def describe(self):
        lines = ["layout=%s mesh=%s" % (self.layout_name, self.mesh_axes)]
        for n in sorted(self.specs):
            extra = ""
            if self.dropped_axes.get(n):
                extra += " dropped=%s" % self.dropped_axes[n]
            if self.fallbacks.get(n):
                extra += " indivisible_dims=%s" % self.fallbacks[n]
            lines.append("  %-48s %-24s rule=%s%s"
                         % (n, self.specs[n], self.rules[n], extra))
        return "\n".join(lines)


class Layout:
    """Named, ordered rule list. First matching rule wins; no match is
    an error (explicit catch-alls only — see module docstring)."""

    def __init__(self, name, rules, data_axes=("dp", "fsdp")):
        self.name = name
        self.rules = list(rules)
        # mesh axes the batch dim shards over (intersected with the
        # actual mesh at resolve time)
        self.data_axes = tuple(data_axes)
        self._cache = {}
        self._cache_lock = threading.Lock()

    def batch_axes(self, mesh):
        """The data axes present in ``mesh`` (batch-dim PartitionSpec
        entry), preserving mesh order."""
        if mesh is None:
            return ()
        return tuple(a for a in mesh.axis_names if a in self.data_axes)

    def resolve(self, params, mesh):
        """Resolve every ``(name, shape)`` in ``params`` against
        ``mesh`` -> :class:`LayoutResolution` (cached: bind once, reuse
        for the life of the process — repeated trainer construction on
        the same model/mesh pays regex matching once).

        Raises :class:`MXNetError` when any parameter matches no rule.
        """
        from .mesh import mesh_shape

        params = tuple((str(n), tuple(int(d) for d in s))
                       for n, s in params)
        axes = mesh_shape(mesh)
        key = (params, tuple(sorted(axes.items())))
        with self._cache_lock:
            hit = self._cache.get(key)
        if hit is not None:
            return hit
        res = self._resolve_uncached(params, axes)
        with self._cache_lock:
            self._cache[key] = res
        return res

    def _resolve_uncached(self, params, axes):
        from jax.sharding import PartitionSpec as P

        res = LayoutResolution(self.name, axes)
        unmatched = []
        for name, shape in params:
            rule = next((r for r in self.rules if r.matches(name, shape)),
                        None)
            if rule is None:
                unmatched.append("%s%r" % (name, shape))
                continue
            entries, dropped, fell = [], [], []
            for dim, entry in enumerate(rule.spec[:len(shape)]):
                ax = (entry,) if isinstance(entry, str) else \
                    tuple(entry or ())
                kept = [a for a in ax if a in axes]
                dropped += [a for a in ax if a not in axes]
                size = 1
                for a in kept:
                    size *= axes[a]
                if kept and shape[dim] % size != 0:
                    # a 10-class bias on fsdp=4: degrade THIS dim only,
                    # and say so in the report
                    fell.append(dim)
                    kept = []
                entries.append(tuple(kept) if len(kept) > 1
                               else (kept[0] if kept else None))
            res.specs[name] = P(*entries)
            res.rules[name] = rule.name
            if dropped:
                res.dropped_axes[name] = sorted(set(dropped))
            if fell:
                res.fallbacks[name] = fell
        if unmatched:
            raise MXNetError(
                "layout %r matched no rule for %d parameter(s): %s — "
                "append an explicit catch-all SpecRule('replicated', "
                "r'.*', ()) if replication is intended (silent "
                "replication is not)"
                % (self.name, len(unmatched), ", ".join(unmatched[:8])))
        return res

    def __repr__(self):
        return "Layout(%r, %d rules, data_axes=%s)" % (
            self.name, len(self.rules), list(self.data_axes))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY = {}
_REGISTRY_LOCK = threading.Lock()


def register_layout(layout, overwrite=False):
    """Register a :class:`Layout` by its name (user overrides: register
    under a new name, or ``overwrite=True`` to replace a built-in)."""
    if not isinstance(layout, Layout):
        raise MXNetError("register_layout takes a Layout, got %s"
                         % type(layout).__name__)
    with _REGISTRY_LOCK:
        if layout.name in _REGISTRY and not overwrite:
            raise MXNetError(
                "layout %r is already registered (pass overwrite=True "
                "to replace it)" % layout.name)
        _REGISTRY[layout.name] = layout
    return layout


def get_layout(name):
    with _REGISTRY_LOCK:
        layout = _REGISTRY.get(name)
    if layout is None:
        raise MXNetError("unknown layout %r (registered: %s)"
                         % (name, sorted(_REGISTRY)))
    return layout


def list_layouts():
    with _REGISTRY_LOCK:
        return sorted(_REGISTRY)


def default_layout_for(mesh):
    """The canonical layout name for a mesh's axes: ``fsdp_tp`` when tp
    is present, ``fsdp`` for an fsdp-only state-sharding mesh, else
    ``data_parallel`` (also the no-mesh answer)."""
    axes = tuple(mesh.axis_names) if mesh is not None else ()
    if "tp" in axes:
        return "fsdp_tp"
    if "fsdp" in axes:
        return "fsdp"
    return "data_parallel"


def resolve_layout(layout=None, mesh=None):
    """Resolve the ``layout=`` argument: an explicit :class:`Layout` or
    registered name wins, else the ``MXNET_LAYOUT`` env default, else
    the canonical layout for the mesh's axes
    (:func:`default_layout_for`)."""
    if isinstance(layout, Layout):
        return layout
    if layout is None:
        from .. import config as _config

        layout = _config.get("MXNET_LAYOUT") or None
    if layout is None:
        layout = default_layout_for(mesh)
    if not isinstance(layout, str):
        raise MXNetError("layout must be a Layout or a registered name, "
                         "got %s" % type(layout).__name__)
    return get_layout(layout)


# ---------------------------------------------------------------------------
# canonical built-ins
# ---------------------------------------------------------------------------

register_layout(Layout("data_parallel", [
    SpecRule("replicated", r".*", ()),
]))

# the decode engine's page pools (generate.PagedGenerationEngine),
# arrays named pool_k/pool_v of rows, (layers * pages * page_size,
# heads * d_head), one row a (layer, token), indexed on dimension 0 by
# the gather and the scatter of every dispatch.  The rows shard over
# the data axes (page ids are host-side bookkeeping and index the whole
# pool, whichever shard holds the row) and heads * d_head, the last
# dimension, over tp — whole heads to a shard, since heads are its
# major factor: each tp shard holds the K/V its column-parallel
# proj_k/v below produce.
# A row count the data axes do not divide (the pool carries a +1 trash
# page) degrades to replicated there while heads stay tp-sharded.
_KV_POOL_FSDP = SpecRule("kv_pool", r"pool_(k|v)$",
                         (("dp", "fsdp"),), min_rank=2)
_KV_POOL_TP_ROWS = SpecRule("kv_pool", r"pool_(k|v)$",
                            (("dp", "fsdp"), "tp"), rank=2)

register_layout(Layout("fsdp", [
    # ZeRO-3: shard dim 0 of every matrix/conv kernel and the only dim
    # of every vector along fsdp; scalars replicated.  Optimizer state
    # follows its parameter (parallel.train places m/v/mom identically).
    _KV_POOL_FSDP,
    SpecRule("matrix_dim0", r".*", ("fsdp",), min_rank=2),
    SpecRule("vector", r".*", ("fsdp",), rank=1),
    SpecRule("scalar", r".*", (), rank=0),
]))

register_layout(Layout("fsdp_tp", [
    _KV_POOL_TP_ROWS,
    # Megatron pairing on the mxnet (out_features, in_features) weight
    # convention: qkv/up projections column-parallel (tp on dim 0), the
    # following out/down projections row-parallel (tp on dim 1), so the
    # activation all-reduce happens once per pair.  fsdp rides the
    # other dim: every matrix is also state-sharded.
    SpecRule("attn_qkv", r"(proj_q|proj_k|proj_v|qkv|query|key|value)"
             r"\d*_weight$", ("tp", "fsdp"), rank=2),
    SpecRule("attn_out", r"(attn_out|proj_out|out_proj)\d*_weight$",
             ("fsdp", "tp"), rank=2),
    SpecRule("ffn_up", r"(ffn_up|fc1|up_proj|gate)\d*_weight$",
             ("tp", "fsdp"), rank=2),
    SpecRule("ffn_down", r"(ffn_down|fc2|down_proj)\d*_weight$",
             ("fsdp", "tp"), rank=2),
    SpecRule("lm_head", r"head\d*_weight$", ("tp", "fsdp"), rank=2),
    SpecRule("embedding", r"embed(ding)?\d*_weight$", ("fsdp", "tp"),
             rank=2),
    SpecRule("matrix_fsdp", r".*", ("fsdp",), min_rank=2),
    SpecRule("vector", r".*", ("fsdp",), rank=1),
    SpecRule("scalar", r".*", (), rank=0),
]))
