"""The serving driver of a model that keeps some layers' rows in pages
and others' in rings (family ``exaone_moe``: sliding-window layers
beside full-attention ones, under the model's own draft module):
``serve_mtp``'s run and comparison, whole, with the caches read the way
such an engine hands them back.

``PagedGenerationEngine.cached`` gives a paged layer's rows of every
position and a windowed layer's as ``{"first": p, "rows": ...}``: the
rows its ring still holds whole, in position order from position ``p``
(the last ``ring rows - spec_k`` of the sequence, as thousands of chunks
longer than the window and verify steps left them, every rejected
draft's row overwritten).  The plain reference keeps no ring: its
``caches`` are every position's ``[K | V]`` of every layer, and a
windowed layer's are cut to the positions the snapshot holds.

* ``cache_rows_gap_max`` takes ``latent_rows_gap_max``'s place: the
  largest ``|rows - ref| / |ref|`` over the two paged layers (the full
  one and the draft module's, whose last row was fed the id after the
  cached ones) and the four rings of ``check_slots`` slots.  Logged
  beside it: ``paged_rows_gap_max`` and ``window_rows_gap_max``.
* ``logit_gap_mean``, ``draft_logit_gap_mean`` and ``wrong_length`` are
  ``serve_mtp``'s own."""
import numpy as np

from benchmark.drivers import serve_hybrid, serve_mtp


def cache_numbers(run, params, taken, quant=None):
    """``serve_mtp.cache_numbers`` for blocks of which some keep a ring.
    With ``quant`` the reference in the control's precision stands in
    the snapshot's place, cut to the same positions."""
    import jax

    from benchmark.lib import weights

    cfg, length = run.cfg, run.traffic["cache_len"]
    fam = weights.family(cfg)
    caches = {None: jax.jit(lambda p, t, n: fam.caches(cfg, p, t, n))}
    if quant is not None:
        caches[quant] = jax.jit(
            lambda p, t, n: fam.caches(cfg, p, t, n, quant))
    paged, rings = [], []
    for snap in taken:
        n = snap["position"]
        if not n:
            continue
        seq = np.zeros((1, length), np.int32)
        seq[0, :n] = snap["tokens"]
        # the draft module's row of the last position was fed the next
        # id; a slot filled to its end has none, and that row is left out
        upto = [n] * len(snap["layers"])
        if n < length and snap["next_token"] is not None:
            seq[0, n] = snap["next_token"]
        else:
            upto[-1] = n - 1
        want = jax.device_get(caches[None](params, seq, np.int32(n)))
        other = None if quant is None else jax.device_get(
            caches[quant](params, seq, np.int32(n)))
        gaps = []
        for li, (mine, ref, k) in enumerate(zip(snap["layers"], want, upto)):
            first = mine["first"] if isinstance(mine, dict) else 0
            got = (mine["rows"] if isinstance(mine, dict) else mine[:k]) \
                if other is None else other[li][0, first:k]
            gaps.append(serve_hybrid._gap(got, ref[0, first:k]))
            (rings if isinstance(mine, dict) else paged).append(gaps[-1])
        run.log("%d positions cached, rows layer by layer (the draft "
                "module's last; a ring's from position %s on): %s" % (
                    n, "/".join(sorted({str(m["first"])
                                        for m in snap["layers"]
                                        if isinstance(m, dict)})) or "-",
                    " ".join("%.4f" % g for g in gaps)))
    return {"cache_rows_gap_max": max(paged + rings, default=None),
            "paged_rows_gap_max": max(paged, default=None),
            "window_rows_gap_max": max(rings, default=None)}


def main(run):
    plain = serve_mtp.cache_numbers
    serve_mtp.cache_numbers = cache_numbers
    try:
        serve_mtp.main(run)
    finally:
        serve_mtp.cache_numbers = plain
