"""The serving driver of a dense model whose linear-attention layers keep
recurrent state and whose full-attention layers keep ``[K | V]`` rows
in pages (family ``olmo_hybrid``): ``serve_hybrid``'s run and
comparison, whole, under the name its rows go by here.

``serve_hybrid`` reads two slots' caches off the engine when the window
has closed and holds every layer's to the reference's ``caches`` of the
same ids: ``state_gap_max`` over the GDN layers' states ``S`` and
``latent_rows_gap_max`` over a paged layer's rows, which here are the
full layers' ``[K | V]`` (K normed), not a latent: compared as
**``rows_gap_max``**.  ``logit_gap_mean`` and ``wrong_length`` are
``serve_hybrid``'s own; the widest gap and ``conv_tail_gap_max`` are
logged."""
from benchmark.drivers import serve_hybrid


def renamed(numbers):
    """``numbers`` with the paged rows' gap under this family's name."""
    out = dict(numbers)
    if "latent_rows_gap_max" in out:
        out["rows_gap_max"] = out.pop("latent_rows_gap_max")
    return out


def main(run):
    serve_hybrid.main(run)
    run.numbers = renamed(run.numbers)
