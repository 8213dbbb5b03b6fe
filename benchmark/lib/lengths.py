"""The one general generator of serving traffic.  A traffic file gives
two clipped log-normal distributions (prompt and answer lengths), a
number of strata and a fixed pairing.  The lengths are the quantile
mid-points of each distribution, paired once by the file's permutation,
so every seed offers the same multiset of requests; the seed only
shuffles the order they are offered in (and draws the token ids)."""
import math
from statistics import NormalDist

import numpy as np


def quantile_midpoints(median, sigma, lo, hi, n):
    """The n mid-point quantiles ((i + 0.5) / n) of a log-normal with the
    given median and sigma, rounded and clipped to [lo, hi]."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def request_shapes(traffic):
    """[(prompt_len, answer_len)] of the mix: the same for every seed."""
    n = traffic["strata"]
    p = quantile_midpoints(n=n, **traffic["prompt_len"])
    a = quantile_midpoints(n=n, **traffic["answer_len"])
    perm = traffic["pairing"]
    if sorted(perm) != list(range(n)):
        raise ValueError("pairing must be a permutation of 0..%d" % (n - 1))
    return [(p[i], a[perm[i]]) for i in range(n)]


def request_stream(traffic, seed, vocab):
    """Endless iterator of (prompt token ids, answer length), cycling over
    the mix's shapes; token ids uniform over the vocabulary, drawn from
    the seed.  Where the file gives an ``order`` (a permutation), every
    seed offers the shapes in that order, so that a window holds the same
    work whatever the seed; without one the order is shuffled from the
    seed, anew each cycle."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    shapes = request_shapes(traffic)
    fixed = traffic.get("order")
    if fixed is not None and sorted(fixed) != list(range(len(shapes))):
        raise ValueError("order must be a permutation of the strata")
    while True:
        for i in (fixed if fixed is not None else
                  rng.permutation(len(shapes))):
            plen, alen = shapes[i]
            yield rng.integers(0, vocab, plen, dtype=np.int32), alen
