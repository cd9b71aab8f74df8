"""Runtime environment-variable config registry.

The reference reads ~25 ``MXNET_*`` env vars at constructor sites via
``dmlc::GetEnv`` (catalog: ``docs/how_to/env_var.md``).  This module is
the single typed registry for the knobs that are meaningful on the TPU
stack, with the same names where behavior carries over and explicit
no-op entries where XLA subsumes the mechanism (documented so reference
users know where their knob went).

Use :func:`get` anywhere a knob is consumed; :func:`describe` prints the
catalog (the analogue of env_var.md).
"""
from __future__ import annotations

import os
from typing import Callable, Dict, NamedTuple


class _Knob(NamedTuple):
    name: str
    default: object
    parse: Callable
    doc: str
    effective: bool   # False => accepted for compat, no effect on TPU
    # how docs render the default when the live value is host-dependent
    # (os.cpu_count() etc.) — regenerating docs/env_vars.md must not
    # bake the generating machine's value in
    doc_default: str = None


def _bool(v):
    return str(v).lower() in ('1', 'true', 'yes', 'on')


_REGISTRY: Dict[str, _Knob] = {}


def _register(name, default, parse, doc, effective=True,
              doc_default=None):
    _REGISTRY[name] = _Knob(name, default, parse, doc, effective,
                            doc_default)


# -- engine ----------------------------------------------------------------
_register('MXNET_ENGINE_TYPE', 'ThreadedEnginePerDevice', str,
          'Execution mode: NaiveEngine = synchronous eager (jit off), '
          'anything else = async (env_var.md:8; engine.cc:13-39). '
          'Consumed at import by engine.set_engine_type.')
_register('MXNET_CPU_WORKER_NTHREADS', os.cpu_count() or 4, int,
          'Host-side engine worker threads (env_var.md:10). Consumed by '
          'engine.NativeEngine.',
          doc_default='os.cpu_count() or 4 — host-dependent')
_register('MXNET_EXEC_BULK_EXEC_TRAIN', True, _bool,
          'Op bulking — XLA fuses whole programs, so this is a no-op '
          'kept for compat (env_var.md).', effective=False)
# -- memory ----------------------------------------------------------------
_register('MXNET_HOST_MEM_POOL_CAP_BYTES', 1 << 33, int,
          'Cap on cached bytes in the native host storage pool '
          '(storage.cc; the analogue of MXNET_GPU_MEM_POOL_RESERVE — '
          'device HBM is owned by XLA).')
_register('MXNET_GPU_MEM_POOL_RESERVE', 5, int,
          'Reference GPU-pool reserve percent; HBM pooling is XLA\'s '
          'job on TPU (env_var.md:20).', effective=False)
# -- kvstore ---------------------------------------------------------------
_register('MXNET_KVSTORE_REDUCTION_NTHREADS', 4, int,
          'Reference CPU tree-reduce threads; reductions are single '
          'fused XLA programs here (env_var.md:45).', effective=False)
_register('MXNET_KVSTORE_BIGARRAY_BOUND', 1000 * 1000, int,
          'Element count above which a dist_sync push key crosses '
          'hosts as its own collective; keys at or below it batch '
          'into one fused all-reduce per push group '
          '(kvstore.py DistKVStore.push; env_var.md:47 — the '
          'reference sharded big arrays across servers instead).')
_register('MXNET_ENABLE_GPU_P2P', True, _bool,
          'Reference CUDA P2P toggle; ICI is always on (comm.h:277).',
          effective=False)
# -- profiler --------------------------------------------------------------
_register('MXNET_PROFILER_AUTOSTART', False, _bool,
          'Start profiling at import and dump on exit '
          '(env_var.md:66-75). Consumed by profiler module init.')
_register('MXNET_PROFILER_MODE', 'symbolic', str,
          'symbolic = jitted programs only, all = include imperative '
          'ops (env_var.md:70).')
_register('MXNET_BACKWARD_DO_MIRROR', False, _bool,
          'Trade compute for memory in backward (env_var.md:56-60; '
          'graph_executor.cc:199-216 mirror pass).  TPU mapping: the '
          'forward graph is wrapped in jax.checkpoint so XLA '
          'rematerializes activations during backward instead of '
          'keeping them in HBM.  MXNET_BACKWARD_MIRROR_POLICY picks '
          'what is kept.')
_register('MXNET_BACKWARD_MIRROR_POLICY', 'dots', str,
          "Remat policy under MXNET_BACKWARD_DO_MIRROR: 'dots' keeps "
          "matmul/conv outputs and recomputes cheap elementwise ops "
          "(closest to the reference mirror, which re-runs activation/"
          "BN-type nodes); 'nothing' rematerializes everything "
          "(max memory saving, ~1.3x step FLOPs).")
# -- cudnn-era knobs -------------------------------------------------------
_register('MXNET_CUDNN_AUTOTUNE_DEFAULT', True, _bool,
          'cuDNN autotune workspace search; XLA autotunes during '
          'compilation, knob kept for compat (env_var.md:79).',
          effective=False)
# -- TPU-stack additions ---------------------------------------------------
_register('MXTPU_DISABLE_PALLAS', False, _bool,
          'Force pure-XLA fallbacks instead of Pallas kernels.')
_register('MXTPU_FORCE_PALLAS_INTERPRET', False, _bool,
          'Run Pallas kernels in interpreter mode (CPU testing).')
_register('MXTPU_ASSUME_TPU', False, _bool,
          'Dispatch to Pallas kernel paths even when no TPU device is '
          'attached — for AOT cross-lowering to TPU on a CPU host '
          '(offline Mosaic verification; tests/test_pallas_lowering.py).')
_register('MXTPU_FUSE', '', str,
          'Step-compiler pass pipeline mode (fuse.py PassManager) for '
          'every symbol entering make_fit_step / Executor / Predictor: '
          "'off' = no rewrites, byte-identical to the unfused program; "
          "'safe' = bit-exact structural passes only (constant "
          "folding, dead-branch pruning, elementwise-epilogue fusion); "
          "'aggressive' = adds the folding/kernel rewrites (conv+BN "
          'weight folding, BN->relu->conv and BN->relu Pallas fusion, '
          'NHWC region growth — rtol-level parity).  Unset means off.  '
          'Per-pass counters land as fuse.pass.* when metrics are on; '
          'tools/check_fusion.py gates parity and the cost_analysis '
          'win.')
_register('MXTPU_FUSE_SKIP', '', str,
          'Comma-separated pass names (fuse.default_passes) excluded '
          'from the MXTPU_FUSE pipeline — per-pass disable for '
          'attribution/bisection (e.g. '
          "MXTPU_FUSE_SKIP=epilogue,nhwc_regions).")
_register('MXTPU_FUSED_FIT', True, _bool,
          'Module.fit fuses forward+backward+optimizer into one compiled '
          'program when the optimizer is functionally expressible. Set 0 '
          'to force the reference-style per-parameter updater loop.')
# -- sync-free fit loop (docs/performance.md) ------------------------------
_register('MXTPU_ASYNC_DEPTH', 2, int,
          'Max in-flight dispatched training steps in the fit loop '
          '(engine.StepWindow): dispatch of step N+1 overlaps device '
          'execution of step N, with backpressure on the oldest step. '
          '1 = fully synchronous stepping (the pre-pipeline behavior).')
_register('MXTPU_DEVICE_FEED', True, _bool,
          'Double-buffered host->device feed: Module.fit wraps the '
          'train iterator in io.DeviceFeedIter, which device_puts '
          'batch N+1 with the executor group\'s sharding on a '
          'background thread while step N runs.  Set 0 to place batch '
          'data synchronously on the step\'s critical path.')
_register('MXTPU_DEVICE_METRICS', True, _bool,
          'Fold EvalMetric accumulation into the compiled train step '
          'for metrics with a device_update form (acc/top_k/ce/mse/'
          'mae/rmse/perplexity): accumulators live as device scalars, '
          'synced to host only at Speedometer log points and epoch end '
          '(the metric.host_syncs counter).  Custom/np-only metrics '
          'fall back to the per-batch numpy path automatically.')
_register('MXTPU_PROFILE', False, _bool,
          'Enable the instrument.py span tracer (framework-wide '
          'Chrome-trace spans: executor, engine sync, kvstore, io, '
          'fit loop; dump with instrument.dump_trace).  Implies '
          'MXTPU_METRICS.  Off: every instrumented path is a no-op.')
_register('MXTPU_METRICS', False, _bool,
          'Enable the instrument.py metrics registry (counters/gauges/'
          'timers: cache hits vs retraces, samples/sec, transfer bytes; '
          'snapshot with instrument.metrics_snapshot) without span '
          'tracing.')
# -- warm-start compile subsystem (docs/performance.md) --------------------
_register('MXTPU_COMPILE_CACHE', '', str,
          'Directory for the persistent compilation cache + AOT warmup '
          'manifest (compile_cache.py): compiled XLA executables are '
          'reused across processes (compile.cache_hits) and every jit '
          'trace records its signature into <dir>/manifest.json for '
          'warm-start replay.  JAX_COMPILATION_CACHE_DIR, when set, '
          'wins over this knob (compile_cache.resolve_cache_dir).  '
          'Neither set: no cache, no manifest, no overhead (the run '
          'scripts fall back to <checkout>/.jax_cache).')
_register('MXTPU_WARM_START', False, _bool,
          'Module.fit pre-compiles the fused train step (and any '
          'manifest-recorded signatures for the same symbol) with '
          'jax.jit(...).lower().compile() on background threads BEFORE '
          'the first batch, overlapping XLA compilation with the '
          'device-feed spin-up; the fit loop then calls the AOT '
          'executables directly (zero hot-path traces for warmed '
          'signatures).  Same as fit(warm_start=True).')
_register('MXTPU_PRECOMPILE_BUCKETS', False, _bool,
          'BucketingModule binds and AOT-compiles every bucket declared '
          'via bucket_keys=[...] at fit start instead of tracing each '
          'bucket lazily the first time its key appears mid-epoch (the '
          'retrace storm executor.xla_traces counts); per-bucket '
          'compiles run on the compile_cache warmup pool.')
# -- dp×tp sharded fit (docs/parallel.md) ----------------------------------
_register('MXTPU_MESH', '', str,
          "Device mesh for Module.fit: '4x2' / 'dp=4,tp=2' / '8' "
          "builds a ('dp','tp') jax.sharding.Mesh over the first dp*tp "
          'local devices and jits the fused train step with '
          'NamedSharding in/out shardings — batch split over dp, '
          'params per MXTPU_PARTITION, optimizer state ZeRO-sharded '
          'over dp (parallel/zero.zero_partition_spec).  Gradient '
          'reductions happen INSIDE the compiled program; a dist '
          'kvstore is demoted to control-plane duties only (barrier, '
          'telemetry, membership).  Same as fit(mesh=...).  Unset: '
          'single-chip fit, bit-for-bit the pre-mesh behavior.')
_register('MXTPU_PARTITION', '', str,
          "Parameter partition policy under MXTPU_MESH: 'replicated' "
          "(default — pure data parallelism) or 'auto' (tensor "
          'parallelism: shard each parameter over the tp axis along '
          'its largest tp-divisible dim; indivisible tensors stay '
          'replicated).  fit(partition=...) additionally accepts a '
          '{name-substring: PartitionSpec} dict.')
# -- resilience (docs/resilience.md) ---------------------------------------
_register('MXTPU_KV_RPC_TIMEOUT', 30.0, float,
          'Per-attempt wait for an async-kvstore RPC reply before the '
          'client retries (resilience.py RetryPolicy; the ps-lite van '
          'resend timeout).')
_register('MXTPU_KV_OP_DEADLINE', 120.0, float,
          'Total wall-clock budget for one async-kvstore operation '
          'including all retries; exceeded => ConnectionError instead '
          'of the seed behavior of blocking forever.')
_register('MXTPU_KV_BARRIER_TIMEOUT', 300.0, float,
          'Deadline for barrier(), client- and server-side: past it the '
          'server replies an error instead of holding the worker '
          '(kvstore_server._barrier_wait).')
_register('MXTPU_KV_DEAD_TIMEOUT', 5.0, float,
          'Heartbeat staleness (seconds) after which the server counts '
          'a rank dead and excludes it from barrier accounting '
          '(kvstore_dist.h:151-160 get_num_dead_node).')
_register('MXTPU_KV_MAX_PENDING', 512, int,
          'Max un-acked pushes a worker may buffer for crash replay '
          'before push() applies backpressure (bounds replay memory).')
_register('MXTPU_KV_RETRY_BASE', 0.05, float,
          'First reconnect/retry backoff (seconds); doubles per attempt '
          'up to MXTPU_KV_RETRY_MAX, scaled by MXTPU_KV_RETRY_JITTER.')
_register('MXTPU_KV_RETRY_MAX', 2.0, float,
          'Backoff ceiling (seconds) for kvstore retry/reconnect.')
_register('MXTPU_KV_RETRY_JITTER', 0.25, float,
          'Uniform jitter fraction added to each backoff delay '
          '(decorrelates worker retry storms after a server restart).')
_register('MXTPU_KV_RECONNECT_DEADLINE', 60.0, float,
          'How long a client keeps redialing a lost kv server before '
          'declaring the connection dead and failing pending ops.')
_register('MXTPU_KV_SERVER_BACKING', '', str,
          'Path the async kv server persists its store + replay '
          'watermarks to (atomic commit per MXTPU_KV_SERVER_SYNC_EVERY '
          'pushes); a restarted server restores from it so worker '
          'replay completes training with no lost pushes.')
_register('MXTPU_KV_SERVER_SYNC_EVERY', 1, int,
          'Persist the server store every N applied pushes when '
          'MXTPU_KV_SERVER_BACKING is set (1 = every push: exactly-once '
          'replay; larger trades durability for throughput).')
_register('MXTPU_ELASTIC', False, _bool,
          'Enable the elastic self-healing plane (elastic.py): the fit '
          'loop watches the kv server\'s membership epoch (dead-rank '
          'eviction + generation numbers), admits replacement ranks '
          'mid-job, propagates cluster health verdicts, and — when no '
          'replacement joins within MXTPU_ELASTIC_WAIT — auto-shrinks '
          'the dp mesh axis instead of stalling (docs/resilience.md '
          '"elastic membership & repair").  Off: every hook is a '
          'single flag check and the server never evicts (the PR-2 '
          'passive dead-rank barrier exclusion only).')
_register('MXTPU_ELASTIC_WAIT', 10.0, float,
          'How long surviving ranks hold a vacancy open for a '
          'replacement worker before agreeing (via the generation '
          'barrier) to repair without it — dp-shrink when a mesh is '
          'active, degraded continue otherwise.')
_register('MXTPU_ELASTIC_POLL', 0.5, float,
          'Membership-poll interval (seconds) of the per-rank elastic '
          'coordinator thread (the membership RPC that also reports '
          'this rank\'s epoch progress).')
_register('MXTPU_ELASTIC_JOIN', False, _bool,
          'This worker is a replacement/spare: instead of claiming '
          'MXTPU_PROCESS_ID, the dist_async store calls the join RPC '
          'and is assigned a vacated rank + the current cluster '
          'generation, then re-seeds from the checkpoint consensus '
          'plus a live-store param pull and enters the fit loop at '
          'the cluster\'s current epoch (docs/resilience.md).')
_register('MXTPU_ELASTIC_JOIN_TIMEOUT', 120.0, float,
          'How long a MXTPU_ELASTIC_JOIN worker polls for a vacancy '
          'before giving up with a ConnectionError (spares launched '
          'with the job park here until a rank dies).')
_register('MXTPU_AUTO_RESUME', False, _bool,
          'fit(checkpoint_prefix=...) resumes from the newest loadable '
          'checkpoint automatically (model.find_latest_checkpoint '
          'validity-checked discovery; the reference required an '
          'explicit --load-epoch).')
_register('MXTPU_FAULTS', '', str,
          'Fault-injection plan for the kvstore transport '
          '(resilience.py grammar: site:action[:p[:arg]] joined by ";" '
          '— drop/delay/sever frames, kill the process at a site). '
          'Unset: every fault hook is a single flag check.')
_register('MXTPU_FAULTS_SEED', 0, int,
          'RNG seed for MXTPU_FAULTS coin flips (deterministic chaos).')
# -- production serving plane (docs/serving.md) ----------------------------
_register('MXTPU_SERVE_MAX_DELAY_MS', 2.0, float,
          'Dynamic-batching flush deadline (milliseconds): a queued '
          'request waits at most this long for the serving batcher to '
          'coalesce more requests before a partial batch is flushed to '
          'the device (serving.deadline_flushes).  0 = flush '
          'immediately (no coalescing beyond what is already queued).')
_register('MXTPU_SERVE_MAX_BATCH', 64, int,
          'Cap on coalesced rows per serving flush — also the largest '
          'pow2 executor bucket the batcher will fill '
          '(compile_cache.pad_to_bucket).  A single request larger '
          'than the cap still executes, as its own batch.')
_register('MXTPU_SERVE_MAX_QUEUE', 1024, int,
          'Admission-control bound on queued serving requests per '
          'model: past it submit() sheds the request with a typed '
          'ServerOverloadedError instead of queueing unboundedly '
          '(serving.shed_total counter) — overload degrades to fast '
          'failures, not latency collapse.')
_register('MXTPU_SERVE_REQUEST_TIMEOUT', 30.0, float,
          'Default wall-clock deadline (seconds) a blocking '
          'ModelServer.predict() waits for its response future before '
          'raising TimeoutError (per-call timeout= overrides).')
_register('MXTPU_SERVE_REPLICAS', 1, int,
          'Default replica count per loaded model (load_model '
          'replicas= overrides): N replicas serve one shared admission '
          'queue from DISJOINT device sets (submeshes carved from the '
          'local devices), each with its own coalescing worker — see '
          'the docs/serving.md fleet section.')
_register('MXTPU_SERVE_SLO_MS', 0.0, float,
          'Serving p99 latency SLO (milliseconds) the replica '
          'autoscaler holds (ModelServer.autoscale default; 0 = no '
          'default — autoscale() then needs an explicit slo_p99_ms). '
          'The autoscaler reads WINDOWED p99 (instrument.hist_delta '
          'of the serving histograms), never lifetime aggregates.')
_register('MXTPU_SERVE_MAX_REPLICAS', 4, int,
          'Autoscaler ceiling on replicas per model (clamped further '
          'to the disjoint-device capacity of the local device set). '
          'At the ceiling the controller shrinks the max batch '
          'instead of adding replicas.')
_register('MXTPU_SERVE_SCALE_INTERVAL', 1.0, float,
          'Autoscaler control-loop period (seconds): each tick reads '
          'one windowed p99/queue-depth/shed sample per watched model '
          'and applies at most one hysteresis-gated scaling decision '
          '(every decision logged as an event).  <= 0 disables the '
          'control thread (tick() can still be driven manually).')
_register('MXTPU_SERVEWATCH', False, _bool,
          'Enable the request-attribution plane (serving/servewatch.py): '
          'every admitted request gets a request id and an exclusive-'
          'bucket span chain (admission_wait / lane_wait / '
          'coalesce_wait / pad / execute / slice_deliver summing to '
          'e2e exactly) recorded as serving.req.* histograms, flush '
          'composition records (peer request ids, bucket, pad waste, '
          'executable signature), latency-histogram exemplars '
          '(request id per le= bucket, exposed in the Prometheus '
          'exposition), and tail postmortems (see '
          'MXTPU_SERVE_TRACE_SLOW_MS).  Implies MXTPU_METRICS; spawns '
          'no threads.  Off: every hook is a single flag check.')
_register('MXTPU_SERVE_TRACE_SLOW_MS', 0.0, float,
          'Tail-forensics threshold (milliseconds): under '
          'MXTPU_SERVEWATCH, a request whose e2e latency breaches it '
          '(or that is shed or errored) commits a durable flight-'
          'record postmortem naming its span chain, the flush it rode '
          '(peer ids, bucket, pad waste), queue/lane depths at '
          'admission, and the autoscaler decisions inside its window '
          '(needs an installed flight recorder — '
          'MXTPU_FLIGHT_RECORDER).  0 = only sheds/errors commit '
          'postmortems.')
_register('MXTPU_SERVE_POSTMORTEM_CAP', 64, int,
          'Upper bound on per-request postmortems committed per '
          'process (servewatch) — under sustained overload every '
          'request breaches, and unbounded flight-record dumps would '
          'become their own tail-latency source.  Past the cap, '
          'serving.postmortems_dropped counts what was suppressed.')
_register('MXTPU_SERVE_SUPERVISE', False, _bool,
          'Enable replica supervision (serving/supervisor.py): a '
          'per-server supervisor watches every batcher worker\'s '
          'flush-progress heartbeat; a worker wedged past '
          'MXTPU_SERVE_WEDGE_MS (or dead on an exception) is '
          'quarantined — detached at the flush boundary, its labeled '
          'latency series dropped so the autoscaler\'s windowed p99 '
          'cannot be poisoned, its in-flight requests re-queued at '
          'the head of their lane exactly once — and a warmed '
          'replacement replica is attached BEFORE the quarantined one '
          'is torn down (serving.quarantines / serving.replays / '
          'serving.replica_recovery_secs).  Off: zero supervision '
          'threads and a single flag check on the serving hot path.')
_register('MXTPU_SERVE_WEDGE_MS', 5000.0, float,
          'No-progress threshold (milliseconds) for replica '
          'supervision: a batcher worker whose in-flight flush has '
          'made no progress for this long is declared wedged and '
          'quarantined.  Set it comfortably above the slowest '
          'legitimate flush (service time of the largest bucket).')
_register('MXTPU_SERVE_SUPERVISE_INTERVAL', 0.2, float,
          'Supervisor poll period (seconds): each tick checks every '
          'supervised model\'s workers for wedge/death.  <= 0 '
          'disables the poll thread (tick() can still be driven '
          'manually — deterministic tests).')
_register('MXTPU_SERVE_DEADLINE_MS', 0.0, float,
          'Default per-request deadline (milliseconds) for '
          'ModelServer.submit(): a request still queued past its '
          'deadline is dropped at coalesce time — never executed '
          'dead — and fails with the typed DeadlineExceededError '
          '(serving.deadline_drops; exempt from the SLO latency '
          'histograms, like errors).  0 = no deadline; per-call '
          'deadline_ms= overrides.')
_register('MXTPU_SERVE_DRAIN_TIMEOUT', 30.0, float,
          'Bound (seconds) on serving drains: unload_model(drain=True) '
          'and ModelServer.drain() stop waiting on worker joins past '
          'it and fail the residual (queued + in-flight-on-a-wedged-'
          'replica) requests with typed errors instead of hanging — '
          'a wedged replica can not hold a drain hostage.')
_register('MXTPU_SERVE_BROWNOUT', False, _bool,
          'Default for the autoscaler\'s graceful-brownout ladder '
          '(watch(brownout=...)): under sustained breach AT capacity '
          'the fleet degrades in documented order — shed the batch '
          'lane, shrink max_batch, serve the smallest bucket — '
          'before interactive traffic is ever shed, each transition '
          'a logged, hysteresis-gated decision '
          '(serving.brownout_level gauge).')
# -- training-health plane (docs/observability.md) -------------------------
_register('MXTPU_HEALTH_SENTINELS', False, _bool,
          'Fold on-device health sentinels into the fused fit step '
          '(health.py): a global non-finite flag over loss/grads, the '
          'global gradient norm and the update-to-weight ratio ride the '
          'compiled program as donated device scalars and drain at the '
          'existing Speedometer/epoch-end metric drains — zero extra '
          'host syncs in steady state (health.host_syncs stays 0).')
_register('MXTPU_HEALTH_ACTION', 'warn', str,
          "What a detected non-finite step triggers at the next drain: "
          "'warn' logs; 'skip_update' additionally masks the optimizer "
          "apply in-program so params/opt-state/metric stay bit-for-bit "
          "at their pre-bad-step values; 'abort' raises "
          "health.TrainingDivergedError carrying the offending step "
          "range (and dumps the flight recorder when installed).")
_register('MXTPU_FLIGHT_RECORDER', '', str,
          'Directory for the crash flight recorder (health.py): a '
          'bounded ring of recent spans + a metrics snapshot is dumped '
          'atomically (resilience.atomic_replace) on exit, SIGTERM/'
          'SIGABRT, TrainingDivergedError, every MXTPU_FAULTS-injected '
          'kill, and as a write-ahead snapshot every '
          'MXTPU_FLIGHT_RECORDER_EVERY metric drains — so a postmortem '
          'exists even for abrupt deaths.  Implies MXTPU_PROFILE '
          '(spans are the payload).  Unset: nothing installed.')
_register('MXTPU_FLIGHT_RECORDER_RING', 256, int,
          'How many recent spans the flight-recorder dump retains '
          '(tail across all thread buffers, non-draining).')
_register('MXTPU_FLIGHT_RECORDER_EVERY', 8, int,
          'Write-ahead flight-recorder snapshot cadence: dump every N '
          'metric drains so a kill -9 still leaves a recent file.')
_register('MXTPU_TELEMETRY', True, _bool,
          'Piggyback a compact metrics delta on the dist_async '
          'heartbeat connection (protocol v2 extension, versioned and '
          'ignored by old servers) so the kv server aggregates a '
          'cluster-wide telemetry view (telemetry RPC, '
          'kvstore.DistAsyncKVStore.telemetry).  Only active when the '
          'instrument metrics registry is on.')
# -- performance-attribution plane (docs/observability.md) -----------------
_register('MXTPU_PERFWATCH', False, _bool,
          'Enable the performance-attribution plane (perfwatch.py): '
          'per-executable XLA cost/memory accounting (xla.* gauges), '
          'live MFU + step-time phase histograms (perf.mfu, '
          'perf.phase.*), and the device-memory ledger (mem.live_bytes/'
          'mem.peak_bytes with per-site attribution).  Implies '
          'MXTPU_METRICS.  Off: every hook is a single flag check.')
_register('MXTPU_STEP_SAMPLE', 0, int,
          'Fully sync every Nth fit step (engine.sync on the step\'s '
          'outputs) to measure honest device-step latency '
          '(perf.step_latency histogram, perf.host_syncs counter, a '
          'perf.step trace span with phase children) without re-'
          'introducing per-batch syncs — exactly ceil(nbatch/N) extra '
          'syncs per epoch, metric.host_syncs untouched.  0 = never '
          'sample.  Requires MXTPU_PERFWATCH.')
_register('MXTPU_PEAK_FLOPS', 0.0, float,
          'Override the chip peak FLOP/s used as the perf.mfu '
          'denominator.  0 = look the attached device kind up in '
          'perfwatch.PEAKS; a kind that is not there (the CPU backend '
          'included) raises, so CPU runs that want an MFU set this.')
# -- communication-attribution plane (docs/observability.md) ---------------
_register('MXTPU_COMMWATCH', False, _bool,
          'Enable the communication-attribution plane (commwatch.py): '
          'per-executable collective accounting from the compiled HLO '
          '(comm.all_reduce/all_gather/reduce_scatter/... count+bytes '
          'gauges, comm.bytes_per_step), the comm-vs-compute roofline '
          'split (perf.comm_fraction against the interconnect peak '
          'table / MXTPU_PEAK_BW), and the cross-rank step-cadence + '
          'barrier-wait histograms the kv server turns into '
          'cluster.step_skew straggler attribution.  Implies '
          'MXTPU_METRICS.  Off: every hook is a single flag check.')
_register('MXTPU_PEAK_BW', 0.0, float,
          'Override the per-chip interconnect peak (bytes/sec, all '
          'links) used as the perf.comm_fraction denominator.  0 = '
          'look the attached device kind up in commwatch.ICI_PEAKS; a '
          'kind that is not there (the CPU backend included) raises.')
_register('MXTPU_SKEW_WARN_PCT', 0.0, float,
          'Cross-rank straggler threshold (percent): when the merged '
          'telemetry view shows the slowest rank\'s mean step time '
          'this far above the cluster median, the health plane logs '
          'the laggard (health.skew_warnings counter) and dumps a '
          'flight record naming it (health.note_skew; requires '
          'MXTPU_COMMWATCH on the workers so comm.step_time rides '
          'the heartbeats).  0 = never warn; the cluster.step_skew '
          'gauge and slowest-rank attribution are published either '
          'way.')
# -- input-pipeline & goodput plane (docs/observability.md) ----------------
_register('MXTPU_IOWATCH', False, _bool,
          'Enable the input-pipeline & goodput attribution plane '
          '(iowatch.py): per-stage iterator histograms '
          '(iowatch.stage.read/decode/batchify/prefetch_wait/'
          'feed_wait/...), queue-depth/occupancy gauges and rolling '
          'iowatch.samples_per_sec/bytes_per_sec throughput, plus the '
          'goodput ledger — every second of Module.fit wall clock '
          'attributed into exclusive buckets (productive step, '
          'input_stall, compile, metric_drain, checkpoint, barrier, '
          'recovery, eval, health_skipped) published as goodput.* '
          'gauges and rendered by tools/explain_goodput.py.  Implies '
          'MXTPU_METRICS.  Off: every hook is a single flag check.')
_register('MXTPU_GOODPUT_FLOOR', 0.0, float,
          'Goodput acceptance floor in [0, 1] for '
          'tools/explain_goodput.py --strict (overridden by --floor): '
          'a run whose goodput.fraction lands below it exits nonzero — '
          'the CI hook for "the job silently became input-bound".  '
          '0 = no floor.')
_register('MXTPU_TELEMETRY_DIR', '', str,
          'Directory where the dist_async kv server serves the merged '
          'cluster telemetry as cluster_status.json plus Prometheus '
          'text exposition cluster_status.prom '
          '(instrument.render_prometheus), rewritten atomically at '
          'most once a second as worker deltas arrive.')
# -- chronicle plane (docs/observability.md) -------------------------------
_register('MXTPU_CHRONICLE', '', str,
          'Enable the chronicle plane (chronicle.py) and name its '
          'journal directory: a background sampler scrapes the '
          'metrics registry every MXTPU_CHRONICLE_EVERY_MS into an '
          'append-only JSONL journal (counters as deltas+rates, '
          'gauges as values, histograms as cumulative-bucket '
          'vectors), segment-rotated under the MXTPU_CHRONICLE_MAX_MB '
          'ring bound with atomic commits, runs the online anomaly '
          'detectors (steps_per_sec / goodput / serving p99 / queue '
          'depth / live-bytes leak slope), and records every '
          'instrument.decision() event for tools/timeline.py.  '
          'Implies MXTPU_METRICS.  Empty (the default): off — zero '
          'threads, every hook a single flag check.')
_register('MXTPU_CHRONICLE_EVERY_MS', 500, int,
          'Chronicle sampler period in milliseconds — how often the '
          'journal takes a registry snapshot and feeds the anomaly '
          'detectors.  Detector latency is quantized by it: a breach '
          'needs a couple of consecutive samples to fire.')
_register('MXTPU_CHRONICLE_MAX_MB', 64, int,
          'Ring bound (MiB) on the chronicle journal directory: when '
          'closed segments push the total past it, the oldest '
          'segments are deleted — the journal is a flight recorder, '
          'not an archive.')
_register('MXTPU_CHRONICLE_DETECT', True, _bool,
          'Run the chronicle plane\'s online anomaly detectors '
          '(median/MAD baselines with hysteresis over '
          'perf.steps_per_sec, goodput.fraction, serving e2e p99, '
          'queue depth, mem.live_bytes slope).  Off: the journal '
          'still records; nothing is judged.')


def get(name):
    """Read a registered knob from the environment (typed)."""
    knob = _REGISTRY[name]
    raw = os.environ.get(name)
    if raw is None:
        return knob.default
    return knob.parse(raw)


def pallas_mode(cpu_default='reference'):
    """Shared Pallas dispatch decision for all kernel modules.

    Returns one of:
      'reference' — use the plain-XLA expression
      'interpret' — run the kernel through the Pallas interpreter
      'kernel'    — compile the real kernel (TPU attached, or
                    MXTPU_ASSUME_TPU for AOT cross-lowering on CPU)

    ``cpu_default`` is what a CPU-only host without any knob gets:
    conv/matmul modules have an exact XLA expression and prefer
    'reference'; flash attention prefers 'interpret' (its reference
    materializes the full score matrix).
    """
    if get('MXTPU_DISABLE_PALLAS'):
        return 'reference'
    if get('MXTPU_FORCE_PALLAS_INTERPRET'):
        return 'interpret'
    if get('MXTPU_ASSUME_TPU'):
        return 'kernel'
    import jax
    if any(d.platform == 'tpu' for d in jax.devices()):
        return 'kernel'
    return cpu_default


def describe(effective_only=False):
    """The env-var catalog (the analogue of docs/how_to/env_var.md)."""
    lines = []
    for knob in sorted(_REGISTRY.values()):
        if effective_only and not knob.effective:
            continue
        status = '' if knob.effective else '  [no-op on TPU]'
        default = knob.doc_default if knob.doc_default is not None \
            else repr(knob.default)
        lines.append('%s (default %s)%s\n    %s'
                     % (knob.name, default, status, knob.doc))
    return '\n'.join(lines)


def list_knobs():
    return sorted(_REGISTRY)
