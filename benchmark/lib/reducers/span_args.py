"""Numbers that the program writes as arguments of its own spans
(``mxnet_tpu.tracing``'s ring, read in-process like
``program_spans.py``): over the spans named ``span`` that end in the
window and carry every argument asked for, either the sum of the
``num`` arguments over the sum of the ``den`` arguments, or the mean of
their ratio span by span; times ``scale``.  ``None`` where the ring is
missing, has evicted records the window needs, or holds no such span
with those arguments (a program that does not write them)."""
from benchmark.lib.reducers import program_spans


def reduce(ctx, span, num, den, mode="ratio_of_sums", scale=1.0):
    found, win = program_spans.ring(), program_spans.window_of(ctx)
    if found is None or win is None:
        return None
    (records, evicted), (w0, w1) = found, win
    if evicted and min(r["t0"] for r in records) > w0:
        return None
    rows = [r["args"] for r in records
            if r["name"] == span and w0 < program_spans.end(r) <= w1
            and r.get("args") and all(k in r["args"] for k in num + den)]
    pairs = [(sum(a[k] for k in num), sum(a[k] for k in den)) for a in rows]
    if mode == "mean_of_ratios":
        ratios = [n / d for n, d in pairs if d]
        return scale * sum(ratios) / len(ratios) if ratios else None
    total = sum(d for _n, d in pairs)
    return scale * sum(n for n, _d in pairs) / total if total else None
