"""Small-batch serving throughput (VERDICT r3 weak #1 / next-round #1).

Measures bs32 ResNet-50 inference through mxnet_tpu.serving.Predictor in
the modes that matter:

- ``host-uint8``: raw uint8 NCHW batches fed from the host, normalized
  on device (the fixed serving path — minimum possible bytes/image over
  the host->device link, uploads overlapped with compute).
- ``device``: input already device-resident (a cache-serving scenario) —
  isolates the compiled chain program's own throughput.
- ``link``: measured upload bandwidth for exactly one batch's bytes,
  giving the physics ceiling  bw / bytes_per_image  that ``host-uint8``
  should saturate; where the link is fast the pipeline becomes
  compute-bound at the ``device`` number instead.

Timing follows docs/perf_notes.md methodology: the clock stops only
after every output batch has been fetched to the host, which cannot
complete before the device work has.

A second mode, ``--load``, is the sustained open-loop harness for the
async tier (docs/serving.md): Poisson arrivals at a swept target QPS
against an AsyncPredictor, one BENCH-comparable JSON line per rate
with p50/p99/p999 latency, shed rate, timeout rate, and goodput.
Open-loop matters: a closed loop self-throttles when the server slows
and hides exactly the overload regime the admission control exists
for.

Usage: python tools/bench_serving.py [--json out.json]
       python tools/bench_serving.py --load --qps 20,50,100 \
           [--duration 5] [--deadline-ms 200] [--replicas 1] \
           [--gateway] [--json docs/serving_load.json]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.gluon.model_zoo import vision  # noqa: E402
from mxnet_tpu.serving import Predictor, uint8_normalizer  # noqa: E402
from mxnet_tpu.serving_async import (AsyncPredictor,  # noqa: E402
                                     DeadlineExceeded, Overloaded,
                                     ServingError)


def ledger_records(results):
    """perf_ledger record(s) for one bench_serving run — the three
    throughput modes of the default run, or one goodput record per
    swept rate for ``--load`` results (detected by the ``sweep`` key).
    The tier-1 schema guard calls this with canned results."""
    from mxnet_tpu import perf_ledger

    recs = []
    if "sweep" in results:
        meta = {k: v for k, v in results.items() if k != "sweep"}
        for row in results["sweep"]:
            fields = dict(meta)
            fields.update(row)
            recs.append(perf_ledger.make_record(
                "serving_load_goodput_qps@%g" % row["target_qps"],
                row["goodput_qps"], "qps", **fields))
        return recs
    for metric, key in (
            ("resnet50_serving_host_uint8_img_s", "host_uint8_img_s"),
            ("resnet50_serving_device_img_s", "device_resident_img_s"),
            ("resnet50_serving_device_top5_img_s", "device_top5_img_s")):
        if results.get(key) is not None:
            recs.append(perf_ledger.make_record(
                metric, results[key], "images/sec", **results))
    return recs


def measure_link_bw(shape, chain=8, reps=2):
    """Upload bandwidth in serving's own regime: a stream of ``chain``
    per-batch async device_puts, forced together by one host fetch."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    force = jax.jit(
        lambda *a: sum(jnp.reshape(t, (-1,))[0].astype(jnp.float32)
                       for t in a))
    xs = [np.random.randint(0, 255, shape, np.uint8)
          for _ in range(chain)]
    ys = [jax.device_put(x, dev) for x in xs]
    float(force(*ys))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        ys = [jax.device_put(x, dev) for x in xs]
        float(force(*ys))
        best = min(best, time.perf_counter() - t0)
    return sum(x.nbytes for x in xs) / best


def run(batch=32, n_batches=32, chain=8, dtype="bfloat16", json_path=None):
    import jax

    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    if dtype == "bfloat16":
        net.cast("bfloat16")
    prep = uint8_normalizer(dtype=dtype)
    raw = np.random.randint(0, 255, (batch, 3, 224, 224), np.uint8)
    pred, _ = Predictor.from_block(net, raw, chain=chain, preprocess=prep)

    results = {"batch": batch, "n_batches": n_batches, "chain": chain,
               "dtype": dtype}

    bw = measure_link_bw(raw.shape, chain=chain)
    ceiling = bw / (raw.nbytes / batch)
    results["link_MBps"] = round(bw / 1e6, 2)
    results["link_ceiling_img_s"] = round(ceiling, 1)
    print("host->device link: %.1f MB/s -> physics ceiling %.0f img/s "
          "at %.3f MB/img uint8"
          % (bw / 1e6, ceiling, raw.nbytes / batch / 1e6), flush=True)

    # --- host-uint8 streaming (the real serving path) ---
    batches = [np.random.randint(0, 255, raw.shape, np.uint8)
               for _ in range(n_batches)]
    list(pred.predict(batches[:chain]))          # warm/compile
    t0 = time.time()
    outs = list(pred.predict(batches))
    dt = time.time() - t0
    assert len(outs) == n_batches and outs[0].shape[0] == batch
    ips = batch * n_batches / dt
    results["host_uint8_img_s"] = round(ips, 1)
    results["link_efficiency"] = round(ips / ceiling, 3) if ceiling else None
    print("host-uint8 : %8.1f img/s  (%.2fs, %d x bs%d)  = %.0f%% of link "
          "ceiling" % (ips, dt, n_batches, batch, 100 * ips / ceiling),
          flush=True)

    # --- device-resident (compiled program throughput) ---
    dev = jax.devices()[0]
    dev_batches = [jax.device_put(b, dev) for b in batches]
    jax.block_until_ready(dev_batches)
    list(pred.predict(dev_batches[:chain]))
    t0 = time.time()
    outs = list(pred.predict(dev_batches))
    dt = time.time() - t0
    ips_dev = batch * n_batches / dt
    results["device_resident_img_s"] = round(ips_dev, 1)
    print("device     : %8.1f img/s  (%.2fs)" % (ips_dev, dt), flush=True)

    # --- device-resident + device-side top-5 (classify-API shape:
    # fetch 5 int32/row instead of 1000 logits — the realistic serving
    # response) ---
    import jax.numpy as jnp

    top5 = Predictor.from_block(
        net, raw, chain=chain, preprocess=prep,
        postprocess=lambda o: jax.lax.top_k(o.astype(jnp.float32), 5)[1])[0]
    list(top5.predict(dev_batches[:chain]))
    t0 = time.time()
    outs5 = list(top5.predict(dev_batches))
    dt = time.time() - t0
    assert outs5[0].shape == (batch, 5)
    ips5 = batch * n_batches / dt
    results["device_top5_img_s"] = round(ips5, 1)
    print("device+top5: %8.1f img/s  (%.2fs)" % (ips5, dt), flush=True)

    anchor = 2086.0  # V100 fp16 bs32, reference docs/faq/perf.md:181-199
    results["anchor_v100_img_s"] = anchor
    results["device_vs_anchor"] = round(ips_dev / anchor, 3)
    print("vs V100 fp16 anchor (%.0f): device %.2fx, host-fed %.2fx"
          % (anchor, ips_dev / anchor, ips / anchor), flush=True)

    from mxnet_tpu import perf_ledger

    for rec in ledger_records(results):
        perf_ledger.emit(rec)

    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote", json_path)
    return results


def _pctl(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def _load_predictor(batch_rows, feat, replicas, chain):
    """Small-MLP AsyncPredictor: the load harness measures queueing
    dynamics (admission, deadlines, shed), not model FLOPs — a big model
    would just move every sweep point into the same saturated regime."""
    import jax

    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(8))
    net.initialize()
    example = np.random.rand(batch_rows, feat).astype(np.float32)
    return AsyncPredictor.from_block(
        net, example, replicas=replicas, chain=chain,
        batch_window_ms=1.0), len(jax.devices())


class _HttpFuture:
    """stdlib-HTTP stand-in for a ServingFuture: one daemon thread per
    request (open loop — submit never blocks on the server), resolving
    to the parsed body or the wire code mapped back onto the typed
    taxonomy (429/503 -> Overloaded, 504/408 -> DeadlineExceeded), so
    the sweep's accounting is transport-agnostic."""

    def __init__(self, host, port, model, payload, deadline_ms):
        import threading

        self.resolved_at = None
        self._out = None
        self._exc = None
        self._done = threading.Event()
        t = threading.Thread(
            target=self._run,
            args=(host, port, model, payload, deadline_ms), daemon=True)
        t.start()

    def _run(self, host, port, model, payload, deadline_ms):
        import http.client

        try:
            conn = http.client.HTTPConnection(host, port, timeout=30)
            headers = {"Content-Type": "application/json",
                       "Content-Length": str(len(payload))}
            if deadline_ms:
                headers["X-Deadline-Ms"] = str(deadline_ms)
            conn.request("POST", "/v1/predict/%s" % model, body=payload,
                         headers=headers)
            resp = conn.getresponse()
            body = resp.read()
            self.resolved_at = time.monotonic()
            if resp.status == 200:
                self._out = json.loads(body)["outputs"]
            elif resp.status == 429:
                self._exc = Overloaded("queue", "HTTP 429")
            elif resp.status == 503:
                self._exc = Overloaded("shutdown", "HTTP 503")
            elif resp.status in (504, 408):
                self._exc = DeadlineExceeded("dispatch",
                                             "HTTP %d" % resp.status)
            else:
                self._exc = ServingError("HTTP %d: %s"
                                         % (resp.status, body[:200]))
            conn.close()
        except Exception as e:
            self.resolved_at = time.monotonic()
            self._exc = ServingError("transport: %s" % e)
        finally:
            self._done.set()

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError("HTTP request unresolved")
        if self._exc is not None:
            raise self._exc
        return self._out

    def cancel(self):
        return False


def run_load(qps_list, duration=5.0, batch_rows=8, feat=16, rows=1,
             chain=8, replicas=1, deadline_ms=200.0, seed=0,
             gateway=False, json_path=None):
    """Open-loop Poisson load sweep against the async tier.

    Per target QPS: submit ``rows``-row requests at exponential
    inter-arrival times for ``duration`` seconds (never waiting on the
    server — open loop), then join every future and report latency
    percentiles over completions plus shed/timeout/error rates over
    offered load.  One BENCH JSON line per rate.

    ``gateway=True`` drives the same sweep over real HTTP: an
    in-process :class:`mxnet_tpu.gateway.Gateway` routes ``load`` to
    the AsyncPredictor and every request rides a stdlib HTTP client
    (shed/timeout/p99 measured at the wire, same perf_ledger records —
    ``transport: "http"`` marks the rows).
    """
    from mxnet_tpu import telemetry as tel

    tel.enable()
    ap, n_devs = _load_predictor(batch_rows, feat, replicas, chain)
    req = np.random.RandomState(seed).rand(rows, feat).astype(np.float32)
    ap.predict(req, timeout=30)            # warm/compile off the clock
    out = {"mode": "open-loop-poisson", "duration_s": duration,
           "rows_per_request": rows, "batch_rows": batch_rows,
           "chain": chain, "replicas": replicas, "devices": n_devs,
           "deadline_ms": deadline_ms, "sweep": []}
    gw = None
    if gateway:
        from mxnet_tpu.gateway import Gateway

        # WFQ sized to the predictor's own pipeline capacity so the
        # gateway measures the backend's admission, not its own
        gw = Gateway(port=0, concurrency=max(16, 2 * chain),
                     queue_depth=256)
        gw.add_route("load", ap, kind="predict")
        payload = json.dumps({"rows": req.tolist()})
        out["transport"] = "http"

        def _submit(batch, deadline_ms=None):
            return _HttpFuture(gw.host, gw.port, "load", payload,
                               deadline_ms)
    else:
        _submit = ap.submit
    try:
        for qps in qps_list:
            rng = np.random.RandomState(seed)
            offered = shed = 0
            inflight = []
            start = time.monotonic()
            next_t = start
            end = start + duration
            while next_t < end:
                now = time.monotonic()
                if now < next_t:
                    time.sleep(next_t - now)
                offered += 1
                t0 = time.monotonic()
                try:
                    inflight.append(
                        (_submit(req, deadline_ms=deadline_ms), t0))
                except ServingError:
                    shed += 1
                next_t += rng.exponential(1.0 / qps)
            lats, timeouts, errors = [], 0, 0
            for fut, t0 in inflight:
                try:
                    fut.result(timeout=30)
                    lats.append(fut.resolved_at - t0)
                except Overloaded:
                    # HTTP transport learns a shed at response time
                    # (429/503), not at submit like in-process
                    shed += 1
                except DeadlineExceeded:
                    timeouts += 1
                except TimeoutError:
                    # future unresolved after 30 s (e.g. --deadline-ms 0
                    # past saturation): count it, keep the sweep's data
                    timeouts += 1
                    fut.cancel()
                except ServingError:
                    errors += 1
            # settle before the next rate: leftover queued/claimed work
            # from this rate must not contaminate the next measurement
            settle_end = time.monotonic() + 10.0
            while ap.stats()["inflight"] > 0 and \
                    time.monotonic() < settle_end:
                time.sleep(0.05)
            lats.sort()
            row = {
                "target_qps": qps,
                "offered": offered,
                "offered_qps": round(offered / duration, 1),
                "completed": len(lats),
                "goodput_qps": round(len(lats) / duration, 1),
                "shed": shed,
                "shed_rate": round(shed / offered, 4),
                "timeouts": timeouts,
                "timeout_rate": round(timeouts / offered, 4),
                "errors": errors,
                "p50_ms": round(1e3 * _pctl(lats, 0.50), 2) if lats
                else None,
                "p99_ms": round(1e3 * _pctl(lats, 0.99), 2) if lats
                else None,
                "p999_ms": round(1e3 * _pctl(lats, 0.999), 2) if lats
                else None,
            }
            out["sweep"].append(row)
            from mxnet_tpu import perf_ledger

            perf_ledger.emit(ledger_records(
                {**{k: v for k, v in out.items() if k != "sweep"},
                 "sweep": [row]})[0])
    finally:
        if gw is not None:
            gw.close(timeout=5)
        ap.close(timeout=30)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1)
        print("wrote", json_path)
    return out


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--n-batches", type=int, default=32)
    p.add_argument("--chain", type=int, default=8)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--json", default=None)
    p.add_argument("--load", action="store_true",
                   help="open-loop Poisson QPS sweep vs AsyncPredictor")
    p.add_argument("--qps", default="20,50,100",
                   help="comma-separated target QPS sweep (--load)")
    p.add_argument("--duration", type=float, default=5.0)
    p.add_argument("--deadline-ms", type=float, default=200.0)
    p.add_argument("--replicas", type=int, default=1)
    p.add_argument("--rows", type=int, default=1,
                   help="rows per request (--load)")
    p.add_argument("--gateway", action="store_true",
                   help="drive the --load sweep over real HTTP "
                   "through an in-process serving gateway")
    a = p.parse_args()
    if a.load:
        run_load([float(q) for q in a.qps.split(",")],
                 duration=a.duration, chain=a.chain,
                 replicas=a.replicas, deadline_ms=a.deadline_ms,
                 rows=a.rows, gateway=a.gateway, json_path=a.json)
    else:
        run(a.batch, a.n_batches, chain=a.chain, dtype=a.dtype,
            json_path=a.json)
