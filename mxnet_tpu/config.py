"""Central MXNET_* environment-flag registry.

Reference parity: ``docs/faq/env_var.md`` — the reference scatters
``dmlc::GetEnv`` calls through the C++ tree; here every recognized knob
is declared once with its parser, default, and TPU-native disposition
(honored / delegated to XLA / not applicable), and ``describe()`` prints
the table.  Unknown ``MXNET_*`` variables in the environment trigger a
one-time warning instead of being silently ignored.
"""
from __future__ import annotations

import os
import warnings

__all__ = ["get", "describe", "FLAGS"]


def _pint(v):
    return int(v)


def _pbool(v):
    return str(v).lower() in ("1", "true", "yes", "on")


def _pfloat(v):
    return float(v)


# name -> (default, parser, disposition, note)
FLAGS = {
    "MXNET_ENGINE_TYPE": (
        "ThreadedEnginePerDevice", str, "honored",
        "NaiveEngine forces synchronous dispatch (race-detection oracle); "
        "anything else keeps jax async dispatch (engine.py)"),
    "MXNET_PROFILER_AUTOSTART": (
        "0", _pbool, "honored", "start the jax trace at import"),
    "MXNET_TEST_PLATFORM": (
        "cpu", str, "honored",
        "test-suite backend selector: 'tpu' runs the op/gluon suites on "
        "the real chip with the cpu<->tpu consistency sweep "
        "(tests/conftest.py)"),
    "MXNET_PROFILER_MODE": (
        "0", _pint, "declared", "recognized; facade config is set via "
        "profiler.set_config"),
    "MXNET_CPU_WORKER_NTHREADS": (
        "4", _pint, "honored",
        "default preprocess_threads for ImageRecordIter"),
    "MXNET_SAFE_ACCUMULATION": (
        "0", _pbool, "honored",
        "accumulate fp16 sum/mean/norm in fp32 (ops/tensor.py)"),
    "MXNET_EXEC_BULK_EXEC_INFERENCE": (
        "1", _pbool, "delegated",
        "operator bulking — XLA fusion always bulks whole programs"),
    "MXNET_EXEC_BULK_EXEC_TRAIN": (
        "1", _pbool, "delegated", "see MXNET_EXEC_BULK_EXEC_INFERENCE"),
    "MXNET_EXEC_ENABLE_ADDTO": (
        "0", _pbool, "delegated",
        "gradient add-to elision — XLA does buffer donation/aliasing"),
    "MXNET_GPU_MEM_POOL_RESERVE": (
        "5", _pint, "delegated",
        "memory pooling is the XLA allocator's job on TPU"),
    "MXNET_GPU_WORKER_NTHREADS": (
        "2", _pint, "n/a", "no CUDA worker threads on TPU"),
    "MXNET_CUDNN_AUTOTUNE_DEFAULT": (
        "1", _pint, "n/a", "no cuDNN on TPU; XLA autotunes convolutions"),
    "MXNET_KVSTORE_REDUCTION_NTHREADS": (
        "4", _pint, "delegated",
        "reduction happens in one jitted program / ICI collective"),
    "MXNET_KVSTORE_BIGARRAY_BOUND": (
        "1000000", _pint, "declared",
        "recognized; the TCP PS does not shard big arrays"),
    "MXNET_ENABLE_GPU_P2P": ("1", _pbool, "n/a", "ICI replaces P2P"),
    "MXNET_UPDATE_ON_KVSTORE": (
        "1", _pbool, "honored", "Module/Trainer update placement"),
    "MXNET_MESH": (
        "", str, "honored",
        "default device-mesh spec for ShardedTrainer/bench front-ends: "
        "'axis=size' pairs over dp/fsdp/pp/ep/sp/mp/tp, e.g. "
        "'dp=2,fsdp=2,tp=2', or 'auto' (all local devices on dp); "
        "'' = no mesh (single-device semantics).  Resolved by "
        "parallel.mesh.resolve_mesh; explicit mesh= arguments win"),
    "MXNET_LAYOUT": (
        "", str, "honored",
        "default parameter-sharding layout name for ShardedTrainer: a "
        "registered spec-rule layout (data_parallel/fsdp/fsdp_tp or "
        "parallel.layout.register_layout additions); '' = pick the "
        "canonical layout for the mesh's axes (fsdp_tp when tp is "
        "present, fsdp for an fsdp axis, else data_parallel)"),
    "MXNET_DTYPE_POLICY": (
        "", str, "honored",
        "default mixed-precision dtype policy for every compile "
        "front-end (Executor/CachedOp/Module/ShardedTrainer/Predictor): "
        "'' or 'f32' = historical f32, 'bf16_mixed' = bf16 compute / "
        "f32 master params + loss scaling + per-layer f32 overrides, "
        "'bf16_pure', or a dtype_policy.register_policy addition.  "
        "Per-site override via dtype_policy="),
    "MXNET_LOSS_SCALE": (
        "65536", _pfloat, "honored",
        "initial dynamic loss scale for loss-scaling dtype policies "
        "(bf16_mixed): the loss is multiplied by the scale before the "
        "backward pass and gradients unscaled after, keeping small "
        "gradients out of the bf16 flush-to-zero band"),
    "MXNET_LOSS_SCALE_GROWTH_INTERVAL": (
        "2000", _pint, "honored",
        "consecutive finite steps before the dynamic loss scale doubles "
        "(capped at MXNET_LOSS_SCALE_MAX)"),
    "MXNET_LOSS_SCALE_BACKOFF": (
        "0.5", _pfloat, "honored",
        "multiplier applied to the loss scale when a scaled step "
        "overflows (the overflowed update is skipped in-graph and "
        "counted, never applied)"),
    "MXNET_LOSS_SCALE_MAX": (
        "16777216", _pfloat, "honored",
        "upper bound for dynamic loss-scale ramp-up (2^24 default)"),
    "MXNET_QUANTIZE_TOPK": (
        "5", _pint, "honored",
        "k for the int8 accuracy gate: tools/quantize_model.py compares "
        "fp32-of-record vs int8 top-k agreement on the recorded "
        "calibration batch before emitting an artifact"),
    "MXNET_QUANTIZE_MAX_DELTA": (
        "0.02", _pfloat, "honored",
        "maximum tolerated top-k accuracy delta (1 - agreement) for the "
        "int8 quantization gate; a larger measured delta refuses the "
        "artifact (tools/quantize_model.py exit code 3)"),
    "MXNET_REMAT_POLICY": (
        "", str, "honored",
        "default activation-remat policy for Executor/CachedOp/"
        "ShardedTrainer ('' = off; see mxnet_tpu.remat.list_policies())"),
    "MXNET_FUSION": (
        "", str, "honored",
        "default graph-fusion policy for Executor/CachedOp/Module/"
        "ShardedTrainer: '' = identical-math patterns + cost-table "
        "upgrades, 'off', 'all', or a pattern-name list "
        "(mxnet_tpu.symbol.fusion.list_patterns())"),
    "MXNET_FUSION_TUNE": (
        "", str, "honored",
        "path to the measured shape-keyed fusion cost table written by "
        "tools/autotune.py ('' = no table: only default-on patterns "
        "fire); override programmatically via config.fusion_cost_table"),
    "MXNET_COMPILE_CACHE": (
        "1", _pbool, "honored",
        "persistent XLA compilation cache: the second process-level run "
        "of the same program loads the executable instead of compiling "
        "it.  JAX_COMPILATION_CACHE_DIR places the cache; unset, it is "
        "<checkout>/.jax_cache (config.COMPILE_CACHE_DIR).  0 = off"),
    "MXNET_AOT": (
        "0", _pbool, "honored",
        "ahead-of-time executable store (aot.py): jit'd hot paths "
        "(Executor, CachedOp, ShardedTrainer.step, serving.Predictor) "
        "lower+compile once and serialize the executable; later "
        "processes deserialize instead of recompiling.  Per-site "
        "override via aot="),
    "MXNET_AOT_DIR": (
        os.path.join(os.path.expanduser("~"), ".cache", "mxnet_tpu",
                     "aot"),
        str, "honored",
        "directory backing the AOT executable store (content-hash "
        "keyed, digest-verified, version-gated; tools/prewarm.py "
        "pre-populates and --check validates it)"),
    "MXNET_AOT_MANIFEST": (
        "1", _pbool, "honored",
        "record every AOT-compiled executable's signature in the "
        "store's manifest.jsonl so tools/prewarm.py --manifest can "
        "rebuild and compile the whole workload ahead of rollout"),
    "MXNET_TRACE": (
        "0", _pbool, "honored",
        "hierarchical span tracing (tracing.py): every layer's spans "
        "(requests, checkpoints, aot:*, fusion:*) with trace/span/parent "
        "IDs into a bounded ring buffer, exportable as one "
        "Chrome/Perfetto trace.json; off = one branch per call site. "
        "The step-level spans of the train and serving loops, gc pauses "
        "and compiles are kept whether or not it is set"),
    "MXNET_TRACE_BUFFER": (
        "131072", _pint, "honored",
        "span ring-buffer capacity (oldest spans evicted first; "
        "evictions counted in mxnet_tpu_trace_spans_dropped_total). "
        "The default holds two and a half minutes of a serving loop of "
        "10 spans a tick at 85 ticks/s (OPT-1.3B's ticks of 12 ms), in "
        "about 64 MiB of host memory when full"),
    "MXNET_FLIGHT_RECORDER": (
        "0", _pbool, "honored",
        "black-box postmortem bundles (trace + telemetry + thread stacks "
        "+ env/backend info) on non-finite guard trips, checkpoint "
        "digest failures, SIGTERM/SIGINT preemption, and unhandled "
        "step/fit/predict exceptions (tracing.record_crash)"),
    "MXNET_FLIGHT_RECORDER_DIR": (
        "", str, "honored",
        "flight-recorder bundle directory ('' = ./flight_recorder)"),
    "MXNET_TELEMETRY": (
        "0", _pbool, "honored",
        "runtime metrics registry (telemetry.py): step/serving/"
        "checkpoint/compile series, Prometheus scrape() + JSON dump(); "
        "off = one flag-check per call site"),
    "MXNET_TELEMETRY_INTERVAL": (
        "30", _pfloat, "honored",
        "TelemetryReporter default snapshot interval in seconds"),
    "MXNET_TELEMETRY_PORT": (
        "0", _pint, "honored",
        "Prometheus HTTP scrape endpoint: serve telemetry.scrape() at "
        "http://0.0.0.0:PORT/metrics with a /healthz readiness probe "
        "for the process lifetime (telemetry.serve_scrape; 0 = off).  "
        "Pair with MXNET_TELEMETRY=1 for non-zero series"),
    "MXNET_EVENTS": (
        "0", _pbool, "honored",
        "wide-event request observability (events.py): one structured "
        "JSONL record per unit of work (serving request, TokenServer "
        "generation, train-step window, checkpoint save/load, AOT "
        "compile/load) with typed outcome, stage latency split, trace "
        "id, and perf_ledger provenance; off = one branch per call "
        "site.  Sheds/deadline/error outcomes are always kept"),
    "MXNET_EVENTS_PATH": (
        "", str, "honored",
        "JSONL file the bounded background event writer appends kept "
        "wide events to (O_APPEND; a full queue drops + counts, never "
        "blocks serving).  '' = in-memory ring only (/requestz and "
        "flight-recorder bundles still see the last 512 events)"),
    "MXNET_EVENTS_SAMPLE": (
        "1.0", _pfloat, "honored",
        "keep probability for ok-outcome wide events below the tail "
        "threshold (head sampling).  Errors, sheds, deadline-exceeded, "
        "evictions and the slowest percentile per kind are ALWAYS "
        "kept regardless of this knob"),
    "MXNET_PERF_LEDGER": (
        "", str, "honored",
        "append-only JSONL run ledger every bench emitter "
        "(bench.py, tools/bench_*.py) writes its schema-versioned "
        "BENCH records into via perf_ledger.emit — the queryable perf "
        "history tools/perf_report.py and tools/perf_gate.py consume "
        "('' = records print but nothing persists)"),
    "MXNET_PEAK_TFLOPS": (
        "", str, "honored",
        "accelerator peak TFLOP/s for the MFU gauge (overrides the "
        "published peak telemetry.DEVICE_PEAKS lists for the device "
        "kind; '' = the table, and no MFU for an unlisted device)"),
    "MXNET_ASYNC_METRICS": (
        "0", _pbool, "honored",
        "non-blocking train-step metrics (parallel/train.py): step() "
        "never syncs on the loss; device-resident accumulators are "
        "pulled by a bounded background fetch and TRAIN_LOSS/heartbeat "
        "consume the last completed fetch.  Hard syncs remain only at "
        "checkpoint/drain boundaries.  Per-trainer override via "
        "async_metrics="),
    "MXNET_STEPS_PER_CALL": (
        "1", _pint, "honored",
        "K-step fused train loop: ShardedTrainer.step_many runs K "
        "pre-staged microbatches as ONE XLA call (lax.scan over a "
        "donated carry), amortizing per-step dispatch.  1 = one program "
        "per step (the historical path).  Per-trainer override via "
        "steps_per_call="),
    "MXNET_DEVICE_PREFETCH": (
        "2", _pint, "honored",
        "default depth of io.DevicePrefetcher: batches whose host->HBM "
        "upload (sharded over the layout's data axes) is staged ahead "
        "of the consuming train step; 0 disables the wrapper "
        "(DataLoader device_prefetch= / io/prefetch.py)"),
    "MXNET_NONFINITE_POLICY": (
        "warn", str, "honored",
        "default step-guard policy for NaN/Inf losses & gradient norms: "
        "off|warn|skip|raise — 'skip' discards the update and keeps the "
        "previous params/optimizer state (checkpoint.nonfinite_policy)"),
    "MXNET_CHECKPOINT_KEEP": (
        "3", _pint, "honored",
        "CheckpointManager keep-last-N retention default"),
    "MXNET_CHECKPOINT_ASYNC": (
        "1", _pbool, "honored",
        "CheckpointManager default save mode: snapshot to host, then "
        "serialize/fsync in a background thread (wait() is the barrier)"),
    "MXNET_CKPT_SHARDED": (
        "0", _pbool, "honored",
        "CheckpointManager default for sharded=: every process writes "
        "only its addressable shards (shard-<host>.npz + digest "
        "sidecar), process 0 commits the global manifest last after "
        "the cross-host durability barrier (pod-scale elastic "
        "checkpoints; see docs/fault_tolerance.md)"),
    "MXNET_DIST_COORDINATOR": (
        "", str, "honored",
        "jax.distributed coordinator address host:port for "
        "parallel.bootstrap_distributed (wins over the legacy "
        "DMLC_PS_ROOT_URI/MXTPU_COORDINATOR spellings); '' means not "
        "configured -> single-process"),
    "MXNET_DIST_NUM_PROCS": (
        "0", _pint, "honored",
        "process count for the jax.distributed bootstrap (<=1 means "
        "single-process; falls back to DMLC_NUM_WORKER/MXTPU_NUM_PROCS)"),
    "MXNET_DIST_PROC_ID": (
        "-1", _pint, "honored",
        "this process's id for the jax.distributed bootstrap (-1 = "
        "unset -> falls back to DMLC_RANK/MXTPU_PROC_ID, then 0)"),
    "MXNET_DIST_CONNECT_RETRIES": (
        "3", _pint, "honored",
        "bootstrap_distributed re-attempts after the first coordinator "
        "connect failure (exponential backoff between attempts)"),
    "MXNET_DIST_CONNECT_BACKOFF": (
        "0.5", _pfloat, "honored",
        "initial backoff seconds between coordinator connect retries "
        "(doubles per attempt, jittered)"),
    "MXNET_DIST_BARRIER_TIMEOUT": (
        "120", _pfloat, "honored",
        "sharded-save durability barrier: seconds process 0 (and every "
        "peer) waits for all shard digest sidecars before the manifest "
        "commit / before giving up on a dead peer"),
    "MXNET_DIST_PREEMPT_GATE": (
        "1", _pint, "honored",
        "coordinated preemption commit: step-boundaries of headroom "
        "between the signalled host's committed step and the pod-wide "
        "final-checkpoint step (bounds host dispatch drift; raise for "
        "deep async pipelines)"),
    "MXNET_FLEET_SPOOL": (
        "", str, "honored",
        "fleet-observatory spool directory (fleet.py): each rank "
        "publishes atomic metric/breakdown/trace snapshots here and "
        "the collector (tools/fleetz.py, /fleetz) merges them into a "
        "pod view with straggler attribution; '' = observatory off"),
    "MXNET_FLEET_INTERVAL": (
        "5", _pfloat, "honored",
        "seconds between background fleet snapshot publishes "
        "(FleetPublisher.start); each publish is one registry collect "
        "+ two atomic file writes into the spool"),
    "MXNET_FLEET_STALE": (
        "30", _pfloat, "honored",
        "fleet collector staleness cut in seconds: a rank whose last "
        "snapshot is older (clock-offset corrected) is marked stale "
        "and excluded from straggler scoring — a dead rank degrades "
        "to a stale row, it never blocks the merge"),
    "MXNET_FLEET_CLOCK_OFFSET": (
        "0", _pfloat, "honored",
        "wall-clock offset in seconds added to every timestamp this "
        "rank's FleetPublisher records — deterministic skew injection "
        "for clock-offset-estimation drills (tests); keep 0 in "
        "production"),
    "MXNET_GLUON_REPO": (
        "", str, "honored",
        "base URL for gluon model_zoo weight downloads (file:// works "
        "for air-gapped mirrors); '' disables downloads "
        "(model_store.get_model_file)"),
    "MXNET_GOODPUT_DIR": (
        "", str, "honored",
        "goodput-ledger job directory (goodput.py): each process "
        "incarnation appends typed wall-clock segments (productive "
        "step, compile, checkpoint save/restore, data wait, startup, "
        "drain) to its own crash-safe JSONL here and the reader "
        "(tools/goodputz.py, /goodputz, perf_report --goodput) merges "
        "every incarnation of every rank into one job-lifetime "
        "goodput/badput report with preemption lost-work pricing; "
        "'' = ledger off"),
    "MXNET_GOODPUT_FLUSH_EVERY": (
        "16", _pint, "honored",
        "goodput-ledger sidecar cadence: records appended between "
        "prefix-digest sidecar commits (GoodputRecorder.flush); the "
        "tail past the last flush is still read best-effort under the "
        "torn-line discipline, so this bounds re-hash work, not data "
        "loss"),
    "MXNET_HOME": (
        os.path.join("~", ".mxnet"), str, "honored",
        "data/cache root for gluon contrib dataset downloads "
        "(gluon/contrib/data.py)"),
    "MXNET_SERVING_QUEUE": (
        "64", _pint, "honored",
        "AsyncPredictor bounded request-queue depth (serving_async.py); "
        "a full queue rejects non-blocking submits with a typed "
        "Overloaded error instead of growing latency without bound"),
    "MXNET_SERVING_DEADLINE_MS": (
        "0", _pfloat, "honored",
        "AsyncPredictor default per-request deadline in milliseconds "
        "(0 = none): expired requests fail with DeadlineExceeded — in "
        "the queue via the sweep, at dispatch pickup, or on late "
        "completion — instead of silently blowing the client timeout"),
    "MXNET_SERVING_MAX_INFLIGHT": (
        "0", _pint, "honored",
        "AsyncPredictor cap on admitted-but-uncompleted requests, "
        "queued + claimed (0 = auto: queue depth + 2 x chain x B x "
        "replicas — pipeline capacity in requests, so it binds when "
        "dispatches are stuck, not before the queue); past it submits "
        "shed with "
        "Overloaded(reason='inflight') or block when backpressure is "
        "requested"),
    "MXNET_SERVING_WARM_POOL": (
        "0", _pint, "honored",
        "AsyncPredictor default warm-pool size: N spare Predictor "
        "replicas pre-built (through the AOT store when enabled) so a "
        "replica ejection swaps a canary-verified spare in "
        "automatically instead of waiting for operator heal()"),
    "MXNET_SERVING_HEAL_PROBE": (
        "0", _pfloat, "honored",
        "seconds between auto-heal canary probes of ejected "
        "AsyncPredictor replicas (0 = no probing): a probe dispatches "
        "one known-good batch and re-admits the replica on success"),
    "MXNET_GATEWAY_PORT": (
        "0", _pint, "honored",
        "HTTP serving gateway listen port (gateway.py; 0 = ephemeral, "
        "the bound port is on Gateway.port).  The gateway also serves "
        "the scrape routes (/metrics /healthz /statusz /varz "
        "/requestz) on the same listener"),
    "MXNET_GATEWAY_MAX_BODY": (
        "1048576", _pint, "honored",
        "gateway request-body byte cap: a Content-Length above it is "
        "refused 413 before reading a byte, so oversized bodies can "
        "never hold a handler thread or its memory"),
    "MXNET_GATEWAY_READ_TIMEOUT_S": (
        "5", _pfloat, "honored",
        "gateway socket read timeout while receiving a request body: "
        "a slow-loris client trickling bytes slower than this is cut "
        "with 408 instead of pinning a handler thread"),
    "MXNET_GATEWAY_QUOTA_QPS": (
        "0", _pfloat, "honored",
        "per-tenant token-bucket refill rate in requests/second "
        "(0 = quotas off): a tenant over its bucket gets 429 with "
        "Retry-After sized to the refill wait"),
    "MXNET_GATEWAY_QUOTA_BURST": (
        "8", _pint, "honored",
        "per-tenant token-bucket capacity: how many requests a tenant "
        "may burst above its steady MXNET_GATEWAY_QUOTA_QPS rate"),
    "MXNET_GATEWAY_QUEUE": (
        "16", _pint, "honored",
        "gateway per-tenant fair-queue depth: a tenant with this many "
        "requests already waiting for a dispatch permit sheds the "
        "next one typed (Overloaded('queue') -> 429)"),
    "MXNET_GATEWAY_CONCURRENCY": (
        "8", _pint, "honored",
        "gateway dispatch permits shared across tenants: concurrent "
        "backend requests; freed permits go to the queued tenant with "
        "the smallest weighted-fair virtual finish time"),
    "MXNET_GATEWAY_DRAIN_S": (
        "10", _pfloat, "honored",
        "gateway close()/SIGTERM drain budget in seconds: /healthz "
        "flips 503 first, new requests shed 503, open streams get "
        "this long to finish before the listener stops"),
    "MXNET_GATEWAY_MAX_TENANTS": (
        "256", _pint, "honored",
        "cap on distinct X-Tenant values tracked by the gateway: "
        "tenants past the cap collapse onto one shared overflow "
        "key (bucket/queue/metric label), so minting unique tenant "
        "headers cannot grow per-tenant state without bound"),
    "MXNET_DECODE_SLOTS": (
        "8", _pint, "honored",
        "generate.PagedGenerationEngine default decode batch slots: "
        "the fixed-shape continuous-batching width of the compiled "
        "decode step (one page-table row per slot)"),
    "MXNET_DECODE_CACHE_LEN": (
        "256", _pint, "honored",
        "default KV-cache positions per slot (prompt + generated, "
        "rounded up to whole pages; capped at the model's max_len).  "
        "A sequence that fills them finishes with reason 'length'"),
    "MXNET_DECODE_QUEUE": (
        "64", _pint, "honored",
        "generate.TokenServer admission-queue depth: a full queue "
        "rejects with the typed Overloaded('queue') error"),
    "MXNET_DECODE_DEADLINE_MS": (
        "0", _pfloat, "honored",
        "default per-request decode deadline (0 = none): an expired "
        "request fails with DeadlineExceeded(stage='prefill'|'decode') "
        "and its cache slot is evicted (reason='deadline')"),
    "MXNET_DECODE_MAX_NEW": (
        "128", _pint, "honored",
        "default cap on generated tokens per request (finish_reason "
        "'length'); per-submit max_new_tokens= overrides"),
    "MXNET_DECODE_PAGE_SIZE": (
        "16", _pint, "honored",
        "positions per KV page in the paged engine's pool; a slot "
        "holds ceil(cache_len/page_size) pages and prefix sharing is "
        "page-aligned (smaller pages share more, dispatch more "
        "scatter rows)"),
    "MXNET_DECODE_PAGES": (
        "0", _pint, "honored",
        "total pages in the paged engine's pool, incl. the reserved "
        "trash page (0 = auto: slots x pages_per_slot + 1, the floor "
        "at which admission-time allocation can never starve a "
        "mid-flight decode)"),
    "MXNET_DECODE_PREFILL_CHUNK": (
        "32", _pint, "honored",
        "chunked-prefill chunk length: prompts stream into the paged "
        "engine this many positions per dispatch, one chunk per "
        "TokenServer loop tick, so a long admission interleaves with "
        "decode steps instead of stalling active lanes' ITL"),
    "MXNET_DECODE_SPEC_K": (
        "0", _pint, "honored",
        "n-gram speculative decoding draft length for the paged "
        "engine (0 = off): each decode step carries up to K drafted "
        "tokens and verifies them in one fixed-shape dispatch; "
        "exact-match acceptance keeps output identical to "
        "non-speculative sampling"),
    "MXNET_DECODE_SPEC_NGRAM": (
        "2", _pint, "honored",
        "suffix length the n-gram speculator matches against the "
        "sequence's own history (prompt + generated) to source drafts"),
    "MXNET_DECODE_PREFIX_SHARE": (
        "1", _pint, "honored",
        "paged-engine prefix sharing: content-hash full prompt pages "
        "and attach later prompts with the same page-aligned prefix "
        "to the cached pages refcounted (copy-on-write by alignment; "
        "0 disables)"),
    "DMLC_ROLE": ("worker", str, "honored", "dist kvstore role"),
    "DMLC_PS_ROOT_URI": ("", str, "honored", "dist kvstore server host"),
    "DMLC_PS_ROOT_PORT": ("9091", _pint, "honored",
                          "dist kvstore server port"),
    "DMLC_WORKER_RANK": ("0", _pint, "honored", "dist worker rank"),
    "DMLC_RANK": ("0", _pint, "honored", "dist rank (fallback name)"),
    "DMLC_NUM_WORKER": ("1", _pint, "honored", "dist worker count"),
    "DMLC_NUM_SERVER": ("1", _pint, "honored", "dist server count"),
}

_warned = set()

#: where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
#: does not place it: one fixed path inside the checkout (.gitignore'd)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def get(name):
    """Parsed value of a registered flag (env overrides default)."""
    default, parser, _disp, _note = FLAGS[name]
    raw = os.environ.get(name, default)
    try:
        return parser(raw)
    except (TypeError, ValueError):
        if name not in _warned:
            _warned.add(name)
            warnings.warn("invalid value %r for %s; using default %r"
                          % (raw, name, default))
        return parser(default)


def warn_unknown():
    """One-time warning for unrecognized MXNET_* environment variables."""
    for name in os.environ:
        if name.startswith("MXNET_") and name not in FLAGS and \
                name not in _warned:
            _warned.add(name)
            warnings.warn("environment variable %s is not recognized by "
                          "mxnet_tpu (see mxnet_tpu.config.FLAGS)" % name)


def describe():
    """Human-readable flag table (reference env_var.md equivalent)."""
    rows = ["%-36s %-9s default=%-10s %s" % (n, d[2], d[0], d[3])
            for n, d in sorted(FLAGS.items())]
    return "\n".join(rows)


def fusion_cost_table(table):
    """Install the process-wide fusion cost table (same switch as the
    ``MXNET_FUSION_TUNE`` env path, callable after import): a JSON
    path, a ``fusion_cost.CostTable``/dict, or None to force no table.
    ``tools/autotune.py`` writes compatible tables."""
    from . import fusion_cost

    fusion_cost.set_cost_table(table)


def enable_aot(store=True):
    """Install the process-wide AOT executable store (same switch as
    ``MXNET_AOT``/``MXNET_AOT_DIR``, callable after import): a store
    directory path, ``True`` (default dir), or ``False`` to force AOT
    off.  Per-site ``aot=`` arguments still override.

    Call BEFORE the first compile when this process should *persist*
    artifacts on CPU: enabling injects the codegen flag that keeps
    serialized CPU executables self-contained, which XLA only honors
    if its flags have not been parsed yet (``MXNET_AOT=1`` in the
    environment gets it unconditionally right — the package bootstrap
    sets the flag at import)."""
    from . import aot

    aot.set_store(store)


def enable_telemetry(on=True):
    """Toggle the runtime metrics registry (same switch as the
    ``MXNET_TELEMETRY`` env flag, callable after import)."""
    from . import telemetry

    if on:
        telemetry.enable()
    else:
        telemetry.disable()


def enable_events(on=True, path=None, sample=None):
    """Toggle wide-event emission (same switch as ``MXNET_EVENTS``;
    ``path``/``sample`` override ``MXNET_EVENTS_PATH`` /
    ``MXNET_EVENTS_SAMPLE``)."""
    from . import events

    if on:
        events.enable(path=path, sample=sample)
    else:
        events.disable()


def enable_tracing(on=True):
    """Toggle hierarchical span tracing (same switch as ``MXNET_TRACE``,
    callable after import)."""
    from . import tracing

    if on:
        tracing.enable()
    else:
        tracing.disable()


def enable_flight_recorder(on=True, directory=None):
    """Toggle the crash flight recorder (same switch as
    ``MXNET_FLIGHT_RECORDER``; ``directory`` overrides
    ``MXNET_FLIGHT_RECORDER_DIR``)."""
    from . import tracing

    if on:
        tracing.enable_flight_recorder(directory)
    else:
        tracing.disable_flight_recorder()


def enable_compile_cache():
    """Place jax's persistent compilation cache; returns its directory.

    Called from package bootstrap unless ``MXNET_COMPILE_CACHE=0``.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it
    and nothing is set here.  Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR` — the directory is part of the cache key,
    so it is one fixed path, never derived from ``~``, a pid or a
    temporary name.  The flag is read at compile time, so this works
    before or after backend init.

    An executable loaded from the cache carries the names and source
    lines it was compiled with, and ``profiler.device_table`` reads a
    trace by them: so the key includes them (by jax's default it leaves
    them out, and a program whose instructions an earlier checkout
    compiled came back with that checkout's scopes, or none), with
    source files named from the checkout's root, so that two checkouts
    of the same files share their entries."""
    import re

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        "^" + re.escape(os.path.dirname(COMPILE_CACHE_DIR) + os.sep))
    return jax.config.jax_compilation_cache_dir


def markdown_table():
    """``docs/env_vars.md`` table body — regenerate that file with
    ``python -m mxnet_tpu.config`` whenever a flag is added (the
    tests/test_env_knobs.py guard fails until it is)."""
    rows = ["| `%s` | %s | `%s` | %s |"
            % (n, d[2], d[0] if d[0] != "" else "''",
               d[3].replace("|", "\\|"))
            for n, d in sorted(FLAGS.items())]
    return "\n".join(["| knob | disposition | default | notes |",
                      "| --- | --- | --- | --- |"] + rows)


if __name__ == "__main__":
    print(markdown_table())
