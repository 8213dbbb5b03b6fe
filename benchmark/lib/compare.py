"""The comparison that decides ``correct``: numbers read from what the
timed path produced, each held against a limit of its own from the
cell's file under ``benchmark/limits/``."""
import statistics
import sys

import numpy as np


def _norm(a):
    return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))


def leaf_gaps(prog, ref, skip=None):
    """Per leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger: the gap between the program's norm
    and the reference's (second order in unbiased rounding noise), and
    the norm of their difference (first order in it, which is what tells
    one precision from the next).  Returns {"norm_gap": (worst, median,
    the four worst leaves), "diff": the same}.  ``skip`` marks leaves
    left out (a gradient that is nought to rounding in the reference)."""
    keep = [i for i in range(len(ref)) if not (skip and skip[i])]
    ref_norms = [_norm(r) for r in ref]
    med = statistics.median(ref_norms[i] for i in keep)
    out = {}
    for key, of in (("norm_gap", lambda p, r, rn: abs(_norm(p) - rn)),
                    ("diff", lambda p, r, rn: _norm(p - r))):
        gaps = sorted(((of(prog[i], ref[i], ref_norms[i])
                        / max(ref_norms[i], med), i) for i in keep),
                      reverse=True)
        out[key] = (gaps[0][0], gaps[len(gaps) // 2][0],
                    [(i, round(g, 4)) for g, i in gaps[:4]])
    return out


def null_gradient_leaves(ref_grads):
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's (a convolution's bias under BatchNorm, a key's bias under
    softmax): they move by round-off alone, and are left out of the
    parameters' change by this rule, never by name."""
    norms = [_norm(g) for g in ref_grads]
    med = statistics.median(norms)
    return [g < 1e-3 * med for g in norms]


def train_numbers(prog, ref):
    """Every reading of the first three steps, compared or not (the cell's
    limits file says which).  ``prog`` and ``ref``: {"losses": [3],
    "grads": [leaf arrays], "deltas": [leaf arrays]}, on the host."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        out["loss_step%d" % (i + 1)] = abs(a - b) / abs(b)
    null = null_gradient_leaves(ref["grads"])
    for key in ("grad", "delta"):
        gaps = leaf_gaps(prog[key + "s"], ref[key + "s"], skip=null)
        for kind, (worst, median, at) in gaps.items():
            out["%s_%s" % (key, kind)] = worst
            out["%s_%s_median" % (key, kind)] = median
            out["%s_%s_worst_leaves" % (key, kind)] = at
    out["leaves_left_out"] = sum(null)
    return out


def judge(numbers, limits):
    """(correct, {name: [number, limit]}): every number with a limit must
    lie at or under it; a number that is missing or not finite fails."""
    rows, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and value == value and value <= limit
        ok = ok and good
        rows[name] = [value, limit]
    return ok, rows


def report(rows, correct, stream=None):
    stream = stream or sys.stderr
    for name, (value, limit) in rows.items():
        print("compared %-22s %s  limit %s  %s" % (
            name, "%.6g" % value if value is not None else "missing", limit,
            "ok" if value is not None and value <= limit else "FAIL"),
            file=stream)
    print("correct %s" % ("true" if correct else "false"), file=stream,
          flush=True)
