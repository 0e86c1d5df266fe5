"""Runtime environment-variable catalogue.

Reference: ``docs/how_to/env_var.md`` + scattered ``dmlc::GetEnv`` reads.
Here every honored variable is declared once with type, default and
documentation; modules read through :func:`get` so the catalogue can never
drift from the implementation. ``mx.env.document()`` renders the table
(the env_var.md analogue) and unknown ``MXNET_*`` variables can be audited
with :func:`check_unknown`.
"""

from __future__ import annotations

import os
from collections import namedtuple

_Var = namedtuple("_Var", ["name", "parse", "default", "doc"])

_CATALOGUE = {}


def _declare(name, parse, default, doc):
    _CATALOGUE[name] = _Var(name, parse, default, doc)


def _parse_bool(v):
    return str(v).lower() not in ("0", "false", "")


_declare("MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
         "Execution engine. 'NaiveEngine' runs every executor in the "
         "synchronous un-jitted interpret mode for debugging (reference "
         "src/engine/engine.cc:14-27); anything else uses the default "
         "lazy + jitted XLA path (the ThreadedEnginePerDevice analogue).")
_declare("MXNET_EXEC_BULK_EXEC_TRAIN", _parse_bool, True,
         "When false, disables the fused fwd+bwd+update single-program "
         "train step; the per-parameter imperative update path runs "
         "instead (reference MXNET_EXEC_BULK_EXEC_TRAIN).")
_declare("MXNET_DEVICE_PREFETCH", _parse_bool, True,
         "When true (default), Module.fit/score wrap the data iterator in "
         "io.DevicePrefetchIter: a staging thread device_puts batch N+1 "
         "with the executor's input shardings while batch N computes (the "
         "iter_prefetcher.h analogue). Set to 0 to feed batches "
         "synchronously from the epoch loop.")
_declare("MXNET_PROFILER_AUTOSTART", _parse_bool, False,
         "Start the profiler at import (reference env_var.md:69-78).")
_declare("MXNET_TELEMETRY", _parse_bool, False,
         "Keep every telemetry.span as an in-memory event as well "
         "(telemetry.events(): name, id, parent, ts, dur). "
         "Counters/gauges/histograms are always on at near-zero cost, and "
         "a trace taken by jax's profiler holds the spans whether or not "
         "this is set; the flag only gates the in-memory event list.")
_declare("MXNET_PROFILER_MODE", str, "symbolic",
         "Profiler mode ('symbolic' or 'all'); recorded in the trace "
         "metadata (XLA traces always cover all device ops).")
_declare("MXNET_COORDINATOR", str, "",
         "host:port of process 0 for multi-host jobs; set by "
         "tools/launch.py (the DMLC_PS_ROOT_URI analogue). Triggers "
         "jax.distributed.initialize at import.")
_declare("MXNET_NUM_PROCS", int, 1,
         "Total processes in the multi-host job (DMLC_NUM_WORKER).")
_declare("MXNET_PROC_ID", int, 0,
         "This process's rank (DMLC_WORKER_ID).")
_declare("MXNET_CPU_WORKER_NTHREADS", int, 4,
         "Host-side worker threads for the decode/augment data plane "
         "(reference MXNET_CPU_WORKER_NTHREADS; default thread-pool size "
         "of ImageRecordIter/ImageDetRecordIter).")
_declare("MXNET_KVSTORE_BIGARRAY_BOUND", int, 1000000,
         "Accepted for reference parity. Reduction here is one XLA "
         "collective regardless of array size, so no server sharding "
         "threshold applies.")
_declare("MXNET_BACKWARD_DO_MIRROR", _parse_bool, False,
         "When true, executors wrap each operator in jax.checkpoint: "
         "backward recomputes each operator's interior; keeps the "
         "residuals an operator names (ops/registry.keep: attention's "
         "output and log-sum-exp, the gated delta rule's U, W and "
         "inverses, MoE's routed rows); compute for activation memory "
         "(reference mirror option, graph_executor.cc:222-280).")
_declare("MXNET_PACK_SMALL_PARAMS", _parse_bool, True,
         "Pack small f32 parameters/aux/grads/optimizer-state tensors "
         "(BN scalars, biases) into one flat device buffer per family at "
         "the training-program boundary — hundreds of tiny XLA boundary "
         "tensors otherwise each pay an async staging copy per step. "
         "Disabled automatically under meshes/sharding, ctx-group "
         "placement and NaiveEngine.")
_declare("MXNET_PP_MICROBATCHES", int, 0,
         "GPipe microbatch count used when SequentialModule lowers to the "
         "pipeline schedule under a 'pp' mesh axis; 0 = the pp degree. "
         "Constructor arg pipeline_microbatches takes precedence.")
_declare("MXNET_PS_PORT", int, 0,
         "Port for the dist_async parameter server (kvstore_async.py); "
         "tools/launch.py allocates and exports it; 0 = coordinator port "
         "+ 512 for hand-launched jobs. The DMLC_PS_ROOT_PORT analogue.")
_declare("MXNET_PS_EXIT_TIMEOUT", float, 3600.0,
         "Seconds rank 0's dist_async server waits at exit for every "
         "worker's done marker before shutting down anyway (stragglers "
         "are the point of async mode, so the default is generous; "
         "launcher-supervised jobs can set it low for fast restarts).")
_declare("MXNET_PS_KEY", str, "",
         "Hex-encoded pre-shared key authenticating every dist_async "
         "wire frame (tools/launch.py generates and exports one per job, "
         "delivered via stdin rather than argv). Empty = unauthenticated "
         "(single-host dev runs).")
_declare("MXNET_PS_MAX_FRAME", int, 1 << 31,
         "Upper bound in bytes on a single dist_async wire frame payload "
         "— a parse-time allocation guard on the typed tensor protocol.")
_declare("MXNET_AOT_CACHE", _parse_bool, False,
         "Persist AOT-compiled executables to disk (MXNET_AOT_CACHE_DIR) "
         "and load them in later processes, keyed by program signature + "
         "backend/jax/framework versions — a warm process binds and runs "
         "with executor.jit_compile == 0. Off by default; enable in "
         "deployments (tools/aot_warm.py pre-populates out of band). "
         "Backends without executable serialization fall back to "
         "trace-and-compile (aot.serialize_unsupported counts it).")
_declare("MXNET_AOT_CACHE_DIR", str, "~/.cache/mxnet_tpu/aot",
         "Directory for the persistent AOT executable cache "
         "(~ expanded; created on first store).")
_declare("MXNET_TRAIN_WINDOW", str, "",
         "Fused-K step depth for Module.fit: an integer K dispatches "
         "train_window(K) chunks; 'auto' probes a few single-step batches "
         "and picks K from the measured dispatch-vs-residual telemetry "
         "ratio (aot.choose_train_window) — deep windows on "
         "dispatch-bound loops, K=1 when device/data-bound. "
         "Windows move lr-schedule and metric updates to window "
         "granularity. Empty (default) keeps the per-batch loop.")
_declare("MXNET_DISPATCH_DEPTH", str, "",
         "Training windows Module.fit keeps IN FLIGHT at once (pipelined "
         "window dispatch): window N+1 is assembled and dispatched while "
         "window N executes, and the host only fences (WindowBoundary."
         "wait) when the in-flight count would exceed this depth. An "
         "integer >= 1 fixes the depth (1 = the pre-pipelining serial "
         "fence per window); empty/'auto' (default) lets the window "
         "scheduler co-tune it with K from the measured dispatch-vs-"
         "residual span ratio (aot.choose_dispatch_depth, >= 2 whenever "
         "windows engage). The decision is published on the "
         "fit.dispatch_depth gauge; policies that must fence every "
         "boundary (MXNET_NONFINITE_GUARD=rollback) cap it at 1 and log "
         "why. Each in-flight window holds K batches of staged inputs, so "
         "device memory scales with depth x K x batch.")
_declare("MXNET_PREFETCH_DEPTH", int, 0,
         "Staging-queue depth (batches) of the DevicePrefetchIter wrapped "
         "around Module.fit/score iterators. 0 (default) = auto: start at "
         "2 and grow to cover dispatch_depth x K + 1 batches when "
         "pipelined training windows engage (the pipeline is only as deep "
         "as the data already staged). An explicit value is honored "
         "as-is.")
_declare("MXNET_NONFINITE_GUARD", str, "",
         "Non-finite-gradient sentinel for training updates: 'skip' folds "
         "a device-side all-finite reduction into the fused train step and "
         "suppresses the whole parameter/optimizer-state/BN-stat update "
         "(lax-select, no per-batch host sync) when any gradient is "
         "NaN/Inf; 'rollback' additionally restores the last checkpoint "
         "after MXNET_NONFINITE_TOLERANCE consecutive skips (then raises "
         "if it happens again); 'raise' fails the fit loop on the first "
         "skipped batch (per-batch host check — debug mode). Empty "
         "(default) = off. Skips are counted in fit.nonfinite_skip; "
         "escalation checks run at epoch boundaries.")
_declare("MXNET_NONFINITE_TOLERANCE", int, 3,
         "Consecutive non-finite-gradient skips tolerated before "
         "MXNET_NONFINITE_GUARD=rollback escalates (restore last "
         "checkpoint, then raise).")
_declare("MXNET_CHECKPOINT_DIR", str, "",
         "When set, Module.fit checkpoints to this directory (crash-"
         "consistent manifested commits, mxnet_tpu.checkpoint) and "
         "auto-resumes from the latest valid checkpoint at fit start — "
         "launch.py --max-restarts relaunches continue mid-training. "
         "Equivalent to fit(checkpoint=CheckpointConfig(dir)).")
_declare("MXNET_CHECKPOINT_PERIOD", int, 1,
         "Epochs between checkpoints (MXNET_CHECKPOINT_DIR).")
_declare("MXNET_CHECKPOINT_KEEP", int, 3,
         "Checkpoints retained (newest first); 0 keeps everything.")
_declare("MXNET_CHECKPOINT_BATCH_PERIOD", int, 0,
         "Additionally checkpoint every N batches mid-epoch (0 = epoch "
         "boundaries only). Mid-epoch checkpoints record the batch cursor "
         "so resume skips already-trained batches.")
_declare("MXNET_CKPT_ASYNC", _parse_bool, False,
         "Run checkpoint file writes on a dedicated writer thread so the "
         "training pause covers only the device-to-host snapshot "
         "(checkpoint.snapshot span); the commit itself overlaps training "
         "(checkpoint.write_async span). Forced off under a multi-worker "
         "dist kvstore, whose two-phase commit is barrier-fenced.")
_declare("MXNET_CKPT_CONSENSUS", _parse_bool, True,
         "Under a multi-worker dist kvstore, resume from the commit rank 0 "
         "verified and broadcast through the kvstore instead of each rank "
         "scanning the checkpoint directory independently (which can "
         "diverge when a scan races a mid-commit rename). Disable only "
         "for debugging.")
_declare("MXNET_IO_RETRY", int, 0,
         "When > 0, Module.fit wraps the training iterator in "
         "io.RetryingIter: transient data-source failures (IOError/OSError/"
         "ConnectionError) are retried up to this many times with "
         "exponential backoff (telemetry io.retry.*) before the exception "
         "propagates.")
_declare("MXNET_IO_RETRY_BACKOFF", float, 0.05,
         "Initial backoff seconds for io.RetryingIter; doubles per "
         "attempt, capped at 30 s.")
_declare("MXNET_IO_POOL", _parse_bool, True,
         "Decode RecordIO batches through the supervised parallel pool "
         "(io_plane.DecodePool): ImageRecordIter/ImageDetRecordIter fan "
         "decode+augment over preprocess_threads workers behind an "
         "ordered reorder buffer that keeps the batch stream "
         "byte-identical to the serial path at a fixed seed. 0 restores "
         "the single-consumer serial decode path (also per-iterator via "
         "use_pool=False).")
_declare("MXNET_IO_QUEUE_DEPTH", int, 0,
         "Bound on decoded-but-unconsumed batches buffered by the decode "
         "pool's reorder buffer (backpressure: workers pause decoding "
         "rather than grow memory). 0 (default) = max(4, "
         "2*preprocess_threads).")
_declare("MXNET_IO_WORKER_TIMEOUT_MS", float, 60000.0,
         "Hung-decode watchdog: when the batch the consumer needs has "
         "been decoding on one worker longer than this, the worker is "
         "abandoned (telemetry io.plane.worker_stall) and its shard "
         "reassigned to a fresh worker (io.plane.worker_restart). 0 "
         "disables the watchdog.")
_declare("MXNET_KV_TIMEOUT", float, 0.0,
         "Seconds a dist kvstore barrier may block before the process "
         "logs actionable diagnostics (rank, peers, likely dead-node "
         "cause) and hard-exits so a supervisor can restart the job — a "
         "stalled collective means a dead peer, and the jax runtime "
         "cannot re-admit single ranks. 0 (default) = wait forever; "
         "tools/launch.py exports 600 for supervised jobs unless already "
         "set.")
_declare("MXNET_KV_TRANSPORT", str, "mesh",
         "Collective transport under the dist kvstore: 'mesh' (default) = "
         "in-process XLA leaders over ICI/DCN, static membership; 'tcp' = "
         "the elastic host-side plane (kvstore_elastic.py) with live "
         "membership epochs — workers may die, lag and join mid-job. "
         "'tcp' also skips jax.distributed.initialize (the jax runtime "
         "pins world size). See docs/distributed.md.")
_declare("MXNET_KV_HEARTBEAT_MS", float, 1000.0,
         "Elastic transport: interval between client heartbeats to the "
         "coordinator (its own socket, so a straggling push never blocks "
         "liveness).")
_declare("MXNET_KV_PEER_TIMEOUT", float, 10.0,
         "Elastic transport: seconds of heartbeat silence after which the "
         "coordinator declares a worker dead, bumps the membership epoch "
         "and completes pending rounds over the survivors — the "
         "MXNET_KV_TIMEOUT watchdog generalized to per-peer liveness.")
_declare("MXNET_KV_RECONNECT", float, 60.0,
         "Elastic transport: total seconds a client retries a broken "
         "coordinator connection (exponential backoff + jitter) before "
         "raising the typed PeerUnreachable instead of hanging. Also "
         "bounds dist_async's server reconnects.")
_declare("MXNET_KV_MAX_STALENESS", int, 0,
         "Elastic transport bounded staleness (SSP): a pull at clock c is "
         "served once round c-S closed, letting fast workers run at most "
         "S rounds ahead of a straggler. 0 = fully synchronous "
         "(dist_sync semantics).")
_declare("MXNET_KV_BACKUP_WORKERS", int, 0,
         "Elastic transport backup-worker mode: close each gradient round "
         "after all-but-N members contributed, dropping the N slowest "
         "contributions (rescaled so the mean gradient stays unbiased; "
         "kvstore.drop_slowest counts). 0 = wait for everyone.")
_declare("MXNET_KV_COMPRESS", str, "",
         "Elastic transport gradient compression on the network leg: "
         "'bf16' or 'int8' (per-tensor max-abs scale), both with "
         "client-side error feedback — the quantization residual is added "
         "to the next push. Master weights and pulls stay f32. Empty = "
         "off.")
_declare("MXNET_FI_KV_KILL_RANK", int, -1,
         "Fault injection (elastic kvstore): rank to kill at train batch "
         "MXNET_FI_KV_KILL_AT_BATCH (-1 = off). The killed worker sends "
         "no LEAVE — death is discovered by heartbeat silence.")
_declare("MXNET_FI_KV_KILL_AT_BATCH", int, -1,
         "Fault injection (elastic kvstore): per-process train-batch "
         "ordinal at which MXNET_FI_KV_KILL_RANK dies (-1 = off).")
_declare("MXNET_FI_KV_DELAY_MS", float, 0.0,
         "Fault injection (elastic kvstore): sleep this long before every "
         "gradient push on MXNET_FI_KV_DELAY_RANK — a straggler, not a "
         "death (it keeps heartbeating). 0 = off.")
_declare("MXNET_FI_KV_DELAY_RANK", int, -1,
         "Fault injection (elastic kvstore): rank MXNET_FI_KV_DELAY_MS "
         "applies to; -1 = every rank.")
_declare("MXNET_FI_KV_DROP_EVERY", int, 0,
         "Fault injection (elastic kvstore): silently drop every Nth "
         "client frame before sending (lost packet; the hardened RPC "
         "layer must retry). 0 = off.")
_declare("MXNET_FI_KV_CORRUPT_EVERY", int, 0,
         "Fault injection (elastic kvstore): flip a byte in every Nth "
         "client frame — the server must detect (crc32/HMAC) and reject "
         "it (kvstore.corrupt_frame_rejected), never absorb it. 0 = off.")
_declare("MXNET_FI_CRASH_AT_BATCH", int, -1,
         "Fault injection: os._exit when the process-global train-batch "
         "ordinal reaches this value (-1 = off). All MXNET_FI_* hooks "
         "apply only on the launcher attempt MXNET_FI_ATTEMPT.")
_declare("MXNET_FI_NAN_BATCHES", str, "",
         "Fault injection: comma-separated train-batch ordinals whose "
         "input data is replaced by NaN (drives a non-finite gradient "
         "through the fused step).")
_declare("MXNET_FI_ITER_RAISE_BATCHES", str, "",
         "Fault injection: batch ordinals at which faultinject.FlakyIter "
         "raises a transient IOError once (retry succeeds).")
_declare("MXNET_FI_CORRUPT_CKPT", str, "",
         "Fault injection: 'truncate' or 'garbage' — damage each "
         "checkpoint's params file right after commit, forcing digest "
         "verification to fall back to the previous valid checkpoint.")
_declare("MXNET_FI_CKPT_KILL_PHASE", str, "",
         "Fault injection: os._exit (kill -9) at a named phase inside the "
         "checkpoint commit — 'mid-shard-write', 'pre-manifest', "
         "'post-manifest-pre-rename' or 'mid-LATEST' — the torn states a "
         "mid-save SIGKILL can leave. Gated by MXNET_FI_ATTEMPT/"
         "MXNET_FI_RANK like every MXNET_FI_* injection.")
_declare("MXNET_NUM_RESTARTS", int, 0,
         "Launcher attempt ordinal, exported by tools/launch.py "
         "--max-restarts relaunches (0 = first life). Read by dead-node "
         "accounting and to scope MXNET_FI_* fault injection to one "
         "attempt.")
_declare("MXNET_FI_ATTEMPT", int, 0,
         "Launcher attempt (MXNET_NUM_RESTARTS value) the MXNET_FI_* "
         "injections apply to; -1 = every attempt.")
_declare("MXNET_FI_RANK", int, -1,
         "Rank (MXNET_PROC_ID) the MXNET_FI_* injections apply to; "
         "-1 = every rank.")
_declare("MXNET_FI_EXIT_CODE", int, 17,
         "Exit code of the injected crash (MXNET_FI_CRASH_AT_BATCH).")
_declare("MXNET_SERVING_BUCKETS", str, "1,4,16,64",
         "Comma-separated batch-size buckets for serving.ModelServer: the "
         "COMPLETE set of inference program shapes. warmup() pre-compiles "
         "one executable per bucket (persisted via MXNET_AOT_CACHE) and "
         "the dynamic batcher coalesces requests up to the largest "
         "bucket, padding partial groups to the smallest covering one — "
         "the request path never compiles.")
_declare("MXNET_SERVING_MAX_DELAY_MS", float, 2.0,
         "Max milliseconds a queued request waits for batch-mates before "
         "a partial bucket dispatches (the batching deadline — the "
         "serving throughput/latency dial). 0 disables the coalescing "
         "wait; requests still batch with whatever queued during the "
         "previous inference.")
_declare("MXNET_SERVING_QUEUE_DEPTH", int, 256,
         "Admission bound for serving.ModelServer: when this many "
         "requests are already queued, submit() sheds immediately with "
         "ServerOverloaded (serving.shed counter) instead of queueing "
         "unboundedly — p99 stays finite under overload.")
_declare("MXNET_SERVING_DEADLINE_MS", float, 0.0,
         "Default per-request serving deadline: a request whose deadline "
         "passes while still queued is dropped with DeadlineExceeded "
         "(serving.deadline_expired) rather than served after the client "
         "gave up. 0 (default) = no deadline; per-request deadline_ms "
         "overrides.")
_declare("MXNET_SERVING_REPLICAS", int, 0,
         "Model replicas in serving.ModelServer, one per mesh device "
         "(jax local devices): every replica holds its own copy of the "
         "per-bucket AOT executables + device-resident weights, and the "
         "dynamic batcher routes each assembled batch to the least-loaded "
         "HEALTHY replica (per-replica circuit breakers, failover "
         "re-dispatch). 0 (default) = auto: all local accelerator devices "
         "on TPU, 1 on CPU (the single-device server). Clamped to the "
         "devices present.")
_declare("MXNET_SERVING_REPLICA_TIMEOUT_MS", float, 0.0,
         "Per-batch execution watchdog for serving replicas: a device "
         "call exceeding this marks the replica suspect (circuit OPEN, "
         "serving.replica.timeout) and the batch fails over to another "
         "healthy replica instead of freezing the dispatch worker. "
         "0 (default) = no watchdog (a hung call waits forever).")
_declare("MXNET_SERVING_MAX_RETRIES", int, 2,
         "Failover re-dispatches of a failed serving batch (after its "
         "first attempt) before the error reaches clients. Retries stay "
         "inside the batch's deadline budget and only apply to execution "
         "faults (idempotent pure forwards) — typed admission errors are "
         "never retried.")
_declare("MXNET_SERVING_HEDGE_MS", float, 0.0,
         "Tail-latency hedging: a serving batch still unanswered after "
         "this many milliseconds is duplicated to a second healthy "
         "replica; the first result wins and the loser is cancelled/"
         "discarded (serving.replica.hedge / hedge_win). 0 (default) = "
         "off. Costs duplicate device work on the hedged tail — size it "
         "at ~p99 of healthy latency.")
_declare("MXNET_SERVING_CB_ERRORS", int, 3,
         "Consecutive errors (or, with MXNET_SERVING_CB_SLOW_MS, "
         "consecutive slow calls) that trip a serving replica's circuit "
         "breaker OPEN (serving.replica.open). An open replica takes no "
         "traffic until a half-open probe succeeds.")
_declare("MXNET_SERVING_CB_PROBE_MS", float, 100.0,
         "Initial half-open backoff of a serving replica's circuit "
         "breaker: after this long OPEN, exactly one live request is "
         "routed through as a probe; success closes the breaker, failure "
         "re-opens it with the backoff doubled (capped at 10 s).")
_declare("MXNET_SERVING_CB_SLOW_MS", float, 0.0,
         "Slow-call threshold for the serving circuit breaker: "
         "successful replica calls slower than this count toward "
         "MXNET_SERVING_CB_ERRORS like errors (a replica that still "
         "answers but 100x late is down for SLO purposes). 0 (default) "
         "= only real errors count.")
_declare("MXNET_SERVING_MAX_BODY_BYTES", int, 64 << 20,
         "HTTP request-body cap for serving/http.py: a POST whose "
         "Content-Length exceeds this is refused with 413 BEFORE the "
         "body is read into memory. 0 disables the cap.")
_declare("MXNET_FI_SERVE_RAISE_REPLICA", str, "",
         "Fault injection (serving chaos): comma-separated replica ids "
         "whose forward raises — kills replica R under traffic (circuit "
         "opens, batches fail over). Re-read per call: clear it to "
         "revive the replica via the half-open probe.")
_declare("MXNET_FI_SERVE_LATENCY_MS", float, 0.0,
         "Fault injection (serving chaos): sleep injected into the "
         "replica forward (watchdog/hedging fuel), on the replica named "
         "by MXNET_FI_SERVE_LATENCY_REPLICA.")
_declare("MXNET_FI_SERVE_LATENCY_REPLICA", int, -1,
         "Replica id the injected serving latency applies to "
         "(-1 = every replica).")
_declare("MXNET_FI_SERVE_FAIL_EVERY", int, 0,
         "Fault injection (serving chaos): fail every Nth serving batch "
         "attempt (process-global ordinal) — intermittent faults the "
         "failover re-dispatch must absorb with zero client errors. "
         "0 = off.")
_declare("MXNET_FI_SERVE_RELOAD_CORRUPT", str, "",
         "Fault injection (serving chaos): comma-separated replica ids "
         "whose hot reload raises mid-swap — the server must eject that "
         "replica (serving.replica.ejected) and keep the pool serving "
         "the new weights on the others.")
_declare("MXNET_FI_IO_CRASH_BATCHES", str, "",
         "Fault injection (decode-pool chaos): comma-separated batch "
         "ordinals whose decode raises a non-data error inside the pool "
         "worker, killing that worker thread — the supervisor must "
         "restart the slot and reassign its shard with no lost or "
         "duplicated records. Fires once per ordinal "
         "(telemetry faultinject.io_crash).")
_declare("MXNET_FI_IO_HANG_BATCHES", str, "",
         "Fault injection (decode-pool chaos): comma-separated batch "
         "ordinals whose decode sleeps MXNET_FI_IO_HANG_MS inside the "
         "pool worker — watchdog fuel for MXNET_IO_WORKER_TIMEOUT_MS. "
         "Fires once per ordinal (telemetry faultinject.io_hang).")
_declare("MXNET_FI_IO_HANG_MS", float, 500.0,
         "Duration of the injected decode hang "
         "(MXNET_FI_IO_HANG_BATCHES).")
_declare("MXNET_SERVING_MESH", str, "auto",
         "Per-replica device-group layout for serving.ModelServer: a "
         "GraftMesh spec for ONE replica's sub-mesh (axis tokens like "
         "'tp2', 'pp4', 'tp2,pp2'). The pool partitions the local "
         "devices into contiguous groups of that size — e.g. 'tp2' on 8 "
         "devices = 4 group-replicas of 2-device tensor parallelism, "
         "'pp4' = 2 replicas of 4-stage GPipe — and every replica hosts "
         "per-bucket sharded predictors on its group. All health/"
         "failover/hedging machinery applies to group-replicas "
         "unchanged. 'auto' (default) keeps one-device replicas "
         "(MXNET_SERVING_REPLICAS semantics).")
_declare("MXNET_SERVING_SEQ_BUCKETS", str, "",
         "Comma-separated sequence-length buckets for variable-length "
         "serving (BucketingModule-style): each request's seq axis "
         "(MXNET_SERVING_SEQ_AXIS) is zero-padded up to the smallest "
         "covering bucket and batched only with same-bucket requests; "
         "warmup() pre-compiles one executable per (batch, seq) bucket "
         "pair. Requires a sym_gen-style ModelServer symbol (the symbol "
         "varies with seq_len). Empty (default) = fixed-shape serving.")
_declare("MXNET_SERVING_SEQ_AXIS", int, 0,
         "Sample axis (batch axis excluded) that MXNET_SERVING_SEQ_BUCKETS "
         "buckets on: 0 = first per-sample axis, i.e. dimension 1 of the "
         "stacked batch — the seq axis of (batch, seq) LSTM inputs.")
_declare("MXNET_SERVING_CANARY_PCT", float, 0.0,
         "Percentage of /predict traffic the serving registry routes to "
         "the registered canary weight set instead of the primary "
         "(deterministic accumulator split, not random — testable). "
         "Responses keep each server's own weight-version stamp, so "
         "clients can see which version answered. 0 (default) = canary "
         "takes no live traffic.")
_declare("MXNET_SERVING_SHADOW", int, 0,
         "Shadow mode for canary serving: 1 duplicates every primary "
         "request to the registered canary/shadow server and discards "
         "the shadow response (errors swallowed, counted as "
         "serving.shadow_error) — the canary sees production traffic "
         "with zero client impact. 0 (default) = off.")
_declare("MXNET_SERVING_WATCH", float, 0.0,
         "Seconds between polls of the serving watch directory's LATEST "
         "pointer (a PR-4 checkpoint dir): when it names a new "
         "checkpoint, ModelServer hot-reloads the weights atomically "
         "between batches without dropping in-flight requests. 0 "
         "(default) = no watching.")
_declare("MXNET_MESH", str, "",
         "Default device-mesh layout every module family binds against "
         "when no mesh is explicitly installed (parallel.with_mesh): axis "
         "tokens <name><size> joined by ',' or 'x', axes dp/tp/pp/sp — "
         "e.g. 'dp2,pp4' runs GPipe stages over pp rank sets of 2 "
         "data-parallel devices each, 'dp2,tp2,pp2' nests tensor "
         "parallelism inside them. One axis may give '*' (or omit its "
         "size) to absorb all remaining devices; 'auto' = every visible "
         "device on dp. Built once per process (GraftMesh.from_env); an "
         "explicitly installed mesh always wins. Empty (default) = no "
         "implicit mesh (single device, or a dp mesh over the Context "
         "list).")
_declare("MXNET_MESH_BACKEND", str, "",
         "jax backend whose devices back the MXNET_MESH mesh (e.g. 'cpu' "
         "to lay a virtual validation mesh over host cores while a TPU "
         "is attached). Empty (default) = the default backend.")
_declare("MXNET_SANITIZER", int, 0,
         "Arm the runtime concurrency sanitizer "
         "(mxnet_tpu.analysis.sanitizer) process-wide: threading locks "
         "are swapped for instrumented wrappers that maintain a "
         "process-wide lock-order graph and report ABBA cycles with "
         "both acquisition stacks (sanitizer.report()). 1 = arm via "
         "sanitizer.maybe_install(). Under pytest the `sanitize`-marked "
         "suites are instrumented by default; 0 there opts out. Read "
         "raw by the sanitizer itself (it must work with the framework "
         "absent); declared here so the catalogue stays complete.")
_declare("MXNET_SANITIZER_HOLD_MS", float, 0.0,
         "Held-too-long threshold for the runtime sanitizer: any "
         "instrumented lock held longer than this many milliseconds is "
         "reported with its acquire stack (who is starving the decode/"
         "serving plane). 0 (default) disables hold tracking — the "
         "acquire-path stack capture it needs is the expensive part of "
         "the sanitizer.")
_declare("MXNET_XLA_FLAGS", str, "",
         "Comma-separated key=value XLA compiler options attached to every "
         "executor program, on every backend (a key the backend does not "
         "know is XLA's error to report). The analogue of the reference's "
         "cuDNN autotune/workspace knobs (MXNET_CUDNN_AUTOTUNE_DEFAULT, "
         "Convolution workspace param). Values parse as bool/int/float when "
         "they look like one, else stay strings — e.g. "
         "'xla_latency_hiding_scheduler=true,xla_tpu_scoped_vmem_limit_kib="
         "65536'. Feeds the AOT env fingerprint and the executable "
         "digests, so persisted AOT caches never serve a program compiled "
         "under different flags.")
_declare("MXNET_CONV_LAYOUT", str, "auto",
         "Device layout for the 2-D conv stack: 'NCHW' keeps the "
         "reference layout end to end; 'NHWC' lowers Convolution/Pooling/"
         "BatchNorm channels-last (the TPU-native layout — channels ride "
         "the 128-wide lanes) with layout conversions only at graph edges "
         "— the logical graph, shapes, weights and checkpoints stay NCHW, "
         "so the two modes are bitwise-interchangeable on integer "
         "lattices; 'auto' (default) picks NHWC on TPU and NCHW "
         "elsewhere. Part of the compile cache key and the AOT env "
         "fingerprint.")


def get(name):
    """Typed value of a declared variable (env override else default)."""
    var = _CATALOGUE[name]
    raw = os.environ.get(name)
    if raw is None:
        return var.default
    try:
        return var.parse(raw)
    except (TypeError, ValueError):
        return var.default


def raw(name):
    """The uninterpreted environ string of a declared variable, or None
    when unset — for the few callers that must distinguish set-empty from
    absent (rank detection, auth keys). The name must still be declared:
    this is the registry-audited spelling of ``os.environ.get``."""
    if name not in _CATALOGUE:
        raise KeyError(f"{name} is not declared in mxnet_tpu.env")
    return os.environ.get(name)


def document():
    """The catalogue as a markdown table (docs/how_to/env_var.md analogue)."""
    lines = ["| Variable | Default | Description |", "|---|---|---|"]
    for var in _CATALOGUE.values():
        lines.append(f"| {var.name} | {var.default!r} | {var.doc} |")
    return "\n".join(lines)


def check_unknown():
    """MXNET_* variables set in the environment but not in the catalogue."""
    return sorted(
        k for k in os.environ
        if k.startswith("MXNET_") and k not in _CATALOGUE
    )
