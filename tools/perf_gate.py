"""Noise-aware perf regression gate over the BENCH run ledger.

Repeated headline benches spread around their mean while real
regressions hide below log tails — this gate makes
"did this PR regress a metric" a nonzero exit code instead of a
judgement call:

* **Bands are seeded from the baseline's own spread**: per metric,
  tolerance = max(--floor, --spread-factor x relative spread of the
  baseline samples).  A metric measured four times at +-0.5% gets a
  tight band; a CPU-noisy one earns a wide one.  ``--tolerance
  metric=0.08`` pins a metric explicitly.
* **Min-of-blocks aware**: multiple records of one metric within one
  run are repeated measurement blocks — each run reduces to its best
  block (max for throughput, min for latency) before comparison,
  mirroring the microbench methodology; the baseline reference is the
  median of per-run bests.
* **Direction comes from the unit** (images/sec, tokens/sec, qps, x
  = higher-better; seconds, ms = lower-better; unknown units fall
  back on the metric name, then higher-better).
* **Failures name the moving bucket**: when a metric regresses and
  both sides carry a step-time ``attribution``, the largest-moving
  bucket (device_compute / compile / aot_load / data_wait /
  host_other) is printed next to the metric — the gate says not just
  *that* the milliseconds went, but *where*.

Stdlib-only (perf_ledger loads standalone, no jax): the gate is a
seconds-level tier-1 smoke on CPU and a sub-second CI step anywhere.

    # candidate = newest run in the ledger, baseline = the rest:
    python tools/perf_gate.py --ledger perf_ledger.jsonl

    # explicit baseline files (legacy driver captures work too):
    python tools/perf_gate.py --baseline baseline.jsonl \
        --candidate perf_ledger.jsonl

Exit codes: 0 = within bands, 1 = regression (metric + bucket named),
2 = unusable input.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from perf_report import backfill_file, group_runs, pl  # noqa: E402

# metrics where a *drop* is the regression vs where a *rise* is.
# Latency units regress UPWARD: the decode tier's TTFT/per-token
# records (tools/bench_decode.py) are the first latency-bound headline
# metrics, and gating them higher-is-better would wave regressions
# through.  The paged-decode levers (ISSUE 16) gate by unit too:
# ``ratio`` (prefix hit rate) and ``tokens/step`` (accepted drafts per
# verify step) regress DOWNWARD, while the interference TTFT rides the
# existing ``ms`` rule.
_HIGHER_BETTER_UNITS = {"images/sec", "img/s", "tokens/sec", "qps", "x",
                        "bool", "flops", "gb/s", "tokens/sec/user",
                        "tokens/s/user", "ratio", "rate", "tokens/step"}
_LOWER_BETTER_UNITS = {"seconds", "s", "ms", "us", "bytes", "ms/token",
                       "ms/request"}


def higher_is_better(metric, unit):
    m = str(metric).lower()
    # goodput regresses DOWNWARD (a drop means more badput), and its
    # pct unit must never drift into a lower-better bucket: name-pin
    # the direction ahead of the unit tables so the intent survives
    # both a default flip and a future "pct" unit rule
    if m == "goodput_pct" or m.endswith("_goodput_pct"):
        return True
    u = str(unit).lower()
    if u in _HIGHER_BETTER_UNITS:
        return True
    if u in _LOWER_BETTER_UNITS:
        return False
    if m.endswith(("_seconds", "_ms", "_latency", "_overhead_ms_per_save",
                   "_bytes", "_ttft_p50", "_ttft_p99", "_interference_p99")):
        return False
    # name fallback for unitless paged-decode levers: hit rates and
    # accepted-drafts-per-step regress downward-is-bad (higher better),
    # which is also the default — listed here so the intent survives a
    # default flip
    return True


def load_records(paths):
    """Records from a mix of JSONL ledgers and legacy run files.  An
    unreadable/unparsable path is reported and skipped — when nothing
    loads the caller exits 2 (unusable input), never 1 (a crashed gate
    must not read as a perf regression in CI)."""
    records = []
    for path in paths:
        try:
            if path.endswith(".jsonl"):
                recs, problems = pl.read_ledger(path)
                for lineno, msg in problems:
                    print("perf_gate: %s:%d: %s" % (path, lineno, msg),
                          file=sys.stderr)
                records.extend(recs)
            else:
                records.extend(backfill_file(path))
        except (OSError, ValueError) as e:
            print("perf_gate: %s: unreadable (%s)" % (path, e),
                  file=sys.stderr)
    return records


def best_per_run(records, better_max):
    """{run_id: (best value, record that scored it)} — the
    min-of-blocks reduction (repeated records within a run are blocks)."""
    best = {}
    pick = max if better_max else min
    for r in records:
        v = r["value"]
        cur = best.get(r["run_id"])
        if cur is None or pick(v, cur[0]) == v:
            best[r["run_id"]] = (v, r)
    return best


def _median(vals):
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def seeded_tolerance(samples, floor, spread_factor):
    """max(floor, spread_factor x relative spread of the baseline) —
    a 0.5% baseline spread seeds a ~1% band under the default factor,
    and the floor keeps single-sample baselines honest."""
    if len(samples) >= 2:
        mean = sum(samples) / len(samples)
        if mean:
            spread = (max(samples) - min(samples)) / abs(mean)
            return max(floor, spread_factor * spread)
    return floor


def moving_bucket(base_rec, cand_rec):
    """(bucket, delta_ms, pct) of the largest-moving attribution
    bucket between two records, or None when either side has no
    attribution recorded."""
    ba = (base_rec.get("attribution") or {}).get("buckets_ms_per_step")
    bb = (cand_rec.get("attribution") or {}).get("buckets_ms_per_step")
    if not ba or not bb:
        return None
    worst = None
    for name in set(ba) | set(bb):
        a, b = float(ba.get(name, 0.0)), float(bb.get(name, 0.0))
        d = b - a
        if worst is None or abs(d) > abs(worst[1]):
            pct = (100.0 * d / a) if a else (100.0 if d else 0.0)
            worst = (name, d, pct)
    return worst


def gate(baseline, candidate, floor=0.02, spread_factor=2.0,
         tolerances=None, metrics=None):
    """Compare candidate records against baseline records.

    Returns (failures, results): ``results`` is one dict per compared
    metric; ``failures`` the regressed subset.  Metrics present on only
    one side are reported but never fail the gate (a new metric is not
    a regression; a vanished one is a schema problem for review)."""
    tolerances = tolerances or {}
    by_metric_base = {}
    for r in baseline:
        by_metric_base.setdefault(r["metric"], []).append(r)
    by_metric_cand = {}
    for r in candidate:
        by_metric_cand.setdefault(r["metric"], []).append(r)

    results, failures = [], []
    for metric in sorted(set(by_metric_base) & set(by_metric_cand)):
        if metrics and metric not in metrics:
            continue
        unit = by_metric_cand[metric][0].get("unit", "")
        hib = higher_is_better(metric, unit)
        base_best = best_per_run(by_metric_base[metric], hib)
        cand_best = best_per_run(by_metric_cand[metric], hib)
        base_samples = [v for v, _r in base_best.values()]
        ref = _median(base_samples)
        tol = tolerances.get(
            metric, seeded_tolerance(base_samples, floor, spread_factor))
        # candidate = the newest run on the candidate side
        cand_run = max(
            cand_best, key=lambda rid: cand_best[rid][1]["time"])
        cand_val, cand_rec = cand_best[cand_run]
        rel = (cand_val - ref) / abs(ref) if ref else 0.0
        regressed = (rel < -tol) if hib else (rel > tol)
        # attribution vs the newest baseline run's BEST-block record —
        # the same min-of-blocks reduction the value comparison used,
        # so a noisy non-best block (say, one with a compile hiccup)
        # cannot misdirect the named bucket
        base_run = max(
            base_best, key=lambda rid: base_best[rid][1]["time"])
        base_rec = base_best[base_run][1]
        bucket = moving_bucket(base_rec, cand_rec) if regressed else None
        res = {"metric": metric, "unit": unit,
               "direction": "higher" if hib else "lower",
               "baseline": ref, "baseline_runs": len(base_samples),
               "candidate": cand_val, "candidate_run": cand_run,
               "delta_pct": 100.0 * rel, "band_pct": 100.0 * tol,
               "regressed": regressed}
        if bucket is not None:
            res["moving_bucket"] = {"name": bucket[0],
                                    "delta_ms": round(bucket[1], 4),
                                    "delta_pct": round(bucket[2], 1)}
        results.append(res)
        if regressed:
            failures.append(res)
    return failures, results


def _parse_tolerances(items):
    out = {}
    for item in items or ():
        if "=" not in item:
            raise ValueError("--tolerance wants metric=relative, got %r"
                             % item)
        k, v = item.split("=", 1)
        out[k] = float(v)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--ledger",
                   help="one ledger holding both sides: candidate = "
                        "newest run, baseline = every earlier run")
    p.add_argument("--baseline", nargs="+", metavar="PATH",
                   help="baseline ledgers/run files (.jsonl or "
                        "pre-schema driver bench captures)")
    p.add_argument("--candidate", nargs="+", metavar="PATH",
                   help="candidate ledger/run file(s); the newest run "
                        "inside is the one gated")
    p.add_argument("--floor", type=float, default=0.02,
                   help="minimum relative tolerance band (default 0.02)")
    p.add_argument("--spread-factor", type=float, default=2.0,
                   help="band = max(floor, factor x baseline relative "
                        "spread) (default 2.0)")
    p.add_argument("--tolerance", action="append", metavar="METRIC=REL",
                   help="pin a metric's band explicitly (repeatable)")
    p.add_argument("--metrics",
                   help="comma list: gate only these metrics")
    p.add_argument("--json", action="store_true",
                   help="machine-readable result object on stdout")
    args = p.parse_args(argv)

    try:
        tolerances = _parse_tolerances(args.tolerance)
    except ValueError as e:
        print("perf_gate: %s" % e, file=sys.stderr)
        return 2
    if args.ledger:
        records = load_records([args.ledger])
        runs = group_runs(records)
        if len(runs) < 2:
            print("perf_gate: ledger %s holds %d run(s); need a "
                  "baseline and a candidate" % (args.ledger, len(runs)),
                  file=sys.stderr)
            return 2
        ids = list(runs)
        candidate = runs[ids[-1]]
        baseline = [r for rid in ids[:-1] for r in runs[rid]]
    elif args.baseline and args.candidate:
        baseline = load_records(args.baseline)
        candidate = load_records(args.candidate)
    else:
        print("perf_gate: pass --ledger, or --baseline ... "
              "--candidate ...", file=sys.stderr)
        return 2
    if not baseline or not candidate:
        print("perf_gate: no usable records (baseline=%d candidate=%d)"
              % (len(baseline), len(candidate)), file=sys.stderr)
        return 2

    metrics = set(args.metrics.split(",")) if args.metrics else None
    failures, results = gate(
        baseline, candidate, floor=args.floor,
        spread_factor=args.spread_factor, tolerances=tolerances,
        metrics=metrics)
    if not results:
        print("perf_gate: no metric measured on both sides",
              file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps({"ok": not failures, "compared": len(results),
                          "failures": failures, "results": results},
                         indent=1, sort_keys=True))
    else:
        for res in results:
            line = ("%s %s: %.6g vs baseline %.6g (%+.2f%%, band "
                    "±%.2f%%, %s-is-better, %d baseline run(s))"
                    % ("FAIL" if res["regressed"] else "PASS",
                       res["metric"], res["candidate"], res["baseline"],
                       res["delta_pct"], res["band_pct"],
                       res["direction"], res["baseline_runs"]))
            mb = res.get("moving_bucket")
            if mb:
                line += ("; largest-moving attribution bucket: %s "
                         "%+.3f ms/step (%+.1f%%)"
                         % (mb["name"], mb["delta_ms"], mb["delta_pct"]))
            elif res["regressed"]:
                line += "; no attribution recorded on both sides"
            print(line)
    if failures:
        print("perf_gate: %d metric(s) regressed beyond their noise "
              "band: %s" % (len(failures),
                            ", ".join(f["metric"] for f in failures)),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
