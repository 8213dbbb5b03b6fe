"""Operations the algorithm needs, from the configuration's sizes alone,
whatever implements it: so no share of a peak can pass 100% because a
later PR took a copy or a cast off the path.  The counts themselves sit
beside each family's plain reference."""
from benchmark.lib.weights import family


def train_flops_per_sample(cfg, traffic):
    """Forward + backward of one sample (an image, or a token), with no
    recomputed operation counted."""
    return family(cfg).train_flops_per_sample(cfg, traffic)


def serve_flops(cfg, token_contexts):
    """Forward operations of every token processed, prompt and output
    alike: ``token_contexts`` holds, for each, the positions cached
    before it."""
    fam = family(cfg)
    return sum(fam.serve_flops_per_token(cfg, int(c)) for c in token_contexts)


def mfu_percent(flops, seconds, chips, peak_flops_per_s):
    return 100.0 * flops / (seconds * chips * peak_flops_per_s)
