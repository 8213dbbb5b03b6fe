"""A prefill chunk's linear-attention scan against its roofline: the
least time of the chunkwise rule over the positions a chunk counts,
the larger of the family's ``gdn_chunk_flops`` over the bf16 peak and
its ``gdn_chunk_bytes`` over the HBM's (the same work whatever
implements the scan), over the device time of the operations traced
under the named scope ``scope`` in the programs the ``span`` spans
launched in the traced span (``device_by_scope``).  The positions a
chunk counts are the mean of ``count_arg`` over the window's
``ring_span`` spans (the traced span and the window are the same loop a
few seconds apart).  ``None`` without a trace, for a family that has no
such counts, and for a program that opens no such scope."""
from benchmark.lib.reducers import device_by_scope, hybrid_hbm_roofline
from benchmark.lib.weights import family


def reduce(ctx, scope, span, ring_span, count_arg):
    fam = family(ctx["cfg"])
    if not ctx.get("planes") or not hasattr(fam, "gdn_chunk_flops"):
        return None
    ms = device_by_scope.reduce(ctx, scope=scope, span=span)
    means = hybrid_hbm_roofline.step_means(ctx, ring_span, [count_arg])
    if not ms or means is None:
        return None
    peaks = ctx["peaks"]
    least = max(fam.gdn_chunk_flops(ctx["cfg"], means[0])
                / peaks["bf16_flops_per_s"],
                fam.gdn_chunk_bytes(ctx["cfg"], means[0])
                / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
