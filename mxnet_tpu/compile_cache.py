"""Warm-start compile subsystem — persistent compilation cache, AOT
warmup manifest, bucket/shape precompile policy.

PR 3 made the steady-state fit loop sync-free; what remains of "time to
useful work" is compile latency: every process pays full XLA traces for
the fused step, BucketingModule traces each bucket lazily the first time
its key appears mid-epoch, and nothing persists compiled artifacts
across runs.  This module is the warm-start half of the ROADMAP's "as
fast as the hardware allows" north star, in three legs:

1. **Persistent cache** — :func:`ensure_persistent_cache` wires JAX's
   persistent compilation cache at the directory :func:`resolve_cache_dir`
   names (``JAX_COMPILATION_CACHE_DIR``, else ``MXTPU_COMPILE_CACHE``,
   else for the run scripts ``<checkout>/.jax_cache``), with the
   compile-time floor dropped to 0 so small CPU-sized programs persist
   too, so a second process reuses compiled executables from disk
   instead of re-invoking XLA.  The cache's monitoring events land in
   the PR-1 instrument registry as ``compile.cache_hits`` /
   ``compile.cache_misses``, and JAX's timings of every trace, lowering,
   backend compile and cache read as ``compile.*_secs`` histograms
   (``_install_listeners``).

2. **AOT warmup manifest** — every jit trace taken through
   :func:`traced` (the executor's forward/fwd+bwd programs, the fused
   fit step) counts ``compile.traces`` and records its signature
   (symbol fingerprint, batch avals, metric fold key, compute dtype)
   into ``<dir>/manifest.json``, committed via
   ``resilience.atomic_replace``.  ``Module.fit(warm_start=True)`` (or
   ``MXTPU_WARM_START=1``) replays the manifest — plus the
   self-evident primary signature from the bound shapes — with
   ``jax.jit(...).lower(...).compile()`` on the warmup pool BEFORE the
   first batch, overlapping XLA compilation with the PR-3
   DeviceFeedIter spin-up.  The resulting AOT executables are what the
   fit loop actually calls (``Module._run_fused``), so a warm process
   takes ZERO hot-path traces for pre-compiled signatures; warmup-pool
   traces are redirected to ``compile.warmup_traces``
   (``instrument.trace_redirect``) and timed as ``compile.warmup_secs``
   with a ``compile.warmup_inflight`` gauge.

3. **Bucket/shape policy** — ``MXTPU_PRECOMPILE_BUCKETS=1`` makes
   ``BucketingModule`` bind + AOT-compile every DECLARED bucket at fit
   start instead of lazily mid-epoch (the retrace storm the
   ``executor.xla_traces`` counter could see but nothing reduced), and
   :func:`pad_to_bucket` is the pow2 shape policy ``Predictor`` uses to
   bound the number of distinct compiled inference shapes (the
   ``compile.shape_buckets`` gauge).

Zero overhead when off: with neither variable set and the metrics
registry off the manifest is never created (recording is one
module-global ``is None`` check, taken only at trace time anyway), no
JAX config is touched, no listener is registered, and no pool thread
exists.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import threading
import time

from . import config, instrument

__all__ = [
    'resolve_cache_dir', 'ensure_persistent_cache', 'cache_dir',
    'manifest_path',
    'fingerprint', 'traced', 'manifest_entries', 'record_entry',
    'jsonable',
    'warm_start', 'warmup_submit',
    'pad_to_bucket', 'sig_key', 'batch_sig',
]

MANIFEST_NAME = 'manifest.json'
# bound the manifest so a pathological shape churn (the exact disease
# pad_to_bucket exists to cure) cannot grow it without limit
MANIFEST_CAP = 512

_lock = threading.Lock()
_cache_dir = None          # installed directory, or None
_manifest = None           # _Manifest once the cache dir is installed
_pool = None
_inflight = 0
_listening = False
_listen_lock = threading.Lock()


# ---------------------------------------------------------------------------
# Leg 1: persistent compilation cache
# ---------------------------------------------------------------------------

# the run scripts' cache when the environment names none: a fixed path
# inside the checkout (git-ignored), because the directory is part of
# the cache key and one that moves never hits
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    '.jax_cache')


def resolve_cache_dir(checkout_default=False):
    """The one rule for where compiled programs persist.  Returns
    ``(directory, jax_owns_it)``:

    - ``JAX_COMPILATION_CACHE_DIR`` wins; JAX reads it itself, so no
      code sets ``jax_compilation_cache_dir`` (``jax_owns_it`` True);
    - else ``MXTPU_COMPILE_CACHE``;
    - else :data:`CHECKOUT_CACHE_DIR` when ``checkout_default`` (the run
      scripts: chip_smoke.py, examples/train_imagenet.py), or
      ``None`` (a plain ``import mxnet_tpu`` writes no cache).
    """
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env, True
    d = config.get('MXTPU_COMPILE_CACHE')
    if d:
        return d, False
    return (CHECKOUT_CACHE_DIR if checkout_default else None), False


def ensure_persistent_cache(checkout_default=False):
    """Install the JAX persistent compilation cache + warmup manifest at
    the :func:`resolve_cache_dir` directory (idempotent; re-reads the
    environment until installed, so a knob exported after import still
    takes).  Returns the directory, or None when none is named."""
    global _cache_dir, _manifest
    if _cache_dir is not None:
        return _cache_dir
    d, jax_owns_it = resolve_cache_dir(checkout_default)
    if not d:
        return None
    with _lock:
        if _cache_dir is not None:
            return _cache_dir
        os.makedirs(d, exist_ok=True)
        import jax
        if not jax_owns_it:
            jax.config.update('jax_compilation_cache_dir', d)
        # the default 1s floor would skip every CPU-sized program — a
        # warm start that only helps big models is not a warm start
        jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
        _install_listeners()
        _manifest = _Manifest(os.path.join(d, MANIFEST_NAME))
        _cache_dir = d
    return _cache_dir


def cache_dir():
    return _cache_dir


def manifest_path():
    return None if _cache_dir is None else \
        os.path.join(_cache_dir, MANIFEST_NAME)


# JAX's timed compile events -> the histogram each lands in
_PHASES = {
    '/jax/core/compile/jaxpr_trace_duration': 'compile.trace_secs',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'compile.lower_secs',
    '/jax/core/compile/backend_compile_duration': 'compile.backend_secs',
}
_CACHE_READ = '/jax/compilation_cache/cache_retrieval_time_sec'
_open = threading.local()     # .stack: [event, seconds inside] per thread


def _install_listeners():
    """Mirror JAX's compile events into the instrument registry, once a
    process: when the persistent cache is installed or the registry
    first turns on, whichever comes first.  Each listener returns after
    one flag test while the registry is off.

    - jax emits a request event at the top of every cached compile and
      a hit event only on retrieval, on the same thread in the same
      call — so ``compile.cache_misses`` is counted eagerly per request
      and un-counted when the hit lands (the transient is invisible
      outside the compile call itself), ``compile.cache_hits`` on the
      hit.
    - JAX times each trace of a function to a jaxpr, each lowering to
      an MLIR module and each backend compile: the histograms
      ``compile.trace_secs``, ``compile.lower_secs`` and
      ``compile.backend_secs``, and every backend compile counts
      ``compile.programs`` (compiled or fetched from the cache).  A
      compile event inside another (a jit run while a function traces)
      is counted once, in the innermost: each histogram takes an
      event's time less that of the compile events inside it, so the
      three together are the wall time spent in them.
    - The backend event holds the persistent cache's read on a hit and
      its write on a miss.  A hit's read is also
      ``compile.cache_read_secs``: the compile proper is
      ``compile.backend_secs`` less ``compile.cache_read_secs``."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        _listening = True
    from jax._src import monitoring

    def on_event(event, **kw):
        if event == '/jax/compilation_cache/compile_requests_use_cache':
            instrument.inc('compile.cache_misses')
        elif event == '/jax/compilation_cache/cache_hits':
            instrument.inc('compile.cache_hits')
            instrument.inc('compile.cache_misses', -1)

    def on_start(event, value, **kw):
        # JAX records a timed event's start time as a scalar
        if not instrument.metrics_enabled():
            return
        if event in _PHASES:
            stack = _open.__dict__.setdefault('stack', [])
            stack.append([event, 0.0])

    def on_duration(event, duration, **kw):
        if not instrument.metrics_enabled():
            return
        name = _PHASES.get(event)
        if name is None:
            if event == _CACHE_READ:
                instrument.observe_hist('compile.cache_read_secs', duration)
            return
        stack = getattr(_open, 'stack', None)
        inside = stack.pop()[1] if stack and stack[-1][0] == event else 0.0
        if stack:
            stack[-1][1] += duration
        instrument.observe_hist(name, max(duration - inside, 0.0))
        if name == 'compile.backend_secs':
            instrument.inc('compile.programs')

    monitoring.register_event_listener(on_event)
    monitoring.register_scalar_listener(on_start)
    monitoring.register_event_duration_secs_listener(on_duration)


instrument.when_metrics_on(_install_listeners)


# ---------------------------------------------------------------------------
# Leg 2: trace recording + warmup manifest
# ---------------------------------------------------------------------------

def jsonable(value):
    """Fold-key/meta normalizer: the JSON round trip turns tuples into
    lists, so comparisons against reloaded manifest entries must run on
    the normalized form."""
    if isinstance(value, (list, tuple)):
        return [jsonable(v) for v in value]
    if isinstance(value, dict):
        return {str(k): jsonable(v) for k, v in value.items()}
    if isinstance(value, (str, int, float, bool)) or value is None:
        return value
    return str(value)


def fingerprint(symbol):
    """Stable identity of a Symbol's computation (sha1 of its JSON
    serialization) — what ties manifest entries to the graph they were
    traced from, across processes."""
    fp = getattr(symbol, '_compile_cache_fp', None)
    if fp is None:
        try:
            fp = hashlib.sha1(symbol.tojson().encode()).hexdigest()[:16]
        except Exception:
            fp = 'unserializable-%d' % id(symbol)
        try:
            symbol._compile_cache_fp = fp
        except Exception:
            pass
    return fp


class _Manifest(object):
    """The on-disk trace inventory: a JSON document of deduplicated
    trace signatures, committed atomically so a crash mid-write cannot
    leave a truncated file for the next warm start to trust."""

    def __init__(self, path):
        self.path = path
        self._lock = threading.Lock()
        self._entries = None
        self._keys = None

    @staticmethod
    def _entry_key(entry):
        return hashlib.sha1(
            json.dumps(entry, sort_keys=True).encode()).hexdigest()

    def _load(self):
        if self._entries is not None:
            return
        entries = []
        try:
            with open(self.path) as f:
                doc = json.load(f)
            if isinstance(doc, dict) and isinstance(doc.get('traces'), list):
                entries = doc['traces']
        except Exception:
            entries = []
        self._entries = entries
        self._keys = {self._entry_key(e) for e in entries}

    def record(self, entry):
        """Append one signature (dedup'd); returns True when new."""
        with self._lock:
            self._load()
            key = self._entry_key(entry)
            if key in self._keys or len(self._entries) >= MANIFEST_CAP:
                return False
            self._keys.add(key)
            self._entries.append(entry)
            self._flush()
            return True

    def _flush(self):
        from . import resilience
        doc = {'version': 1, 'traces': self._entries}
        with resilience.atomic_replace(self.path) as tmp:
            with open(tmp, 'w') as f:
                json.dump(doc, f, indent=1, sort_keys=True)
        instrument.set_gauge('compile.manifest_entries',
                             len(self._entries))

    def entries(self, kind=None, fp=None):
        with self._lock:
            self._load()
            return [e for e in self._entries
                    if (kind is None or e.get('kind') == kind)
                    and (fp is None or e.get('fp') == fp)]


def manifest_entries(kind=None, fp=None):
    """Recorded trace signatures (empty when no cache dir installed)."""
    if _manifest is None:
        return []
    return _manifest.entries(kind, fp)


def record_entry(entry):
    """Record one arbitrary (JSON-able) entry into the warmup manifest
    — the performance plane files per-executable cost/memory rows
    (kind 'xla_cost') here so a later process knows the cost model
    before compiling.  No-op (False) when no cache dir is installed;
    never raises."""
    if _manifest is None:
        return False
    try:
        return _manifest.record(jsonable(entry))
    except Exception:
        return False


def traced(kind, symbol, fn, counter='executor.xla_traces', meta=None,
           batch_argnum=None):
    """Wrap ``fn`` for ``jax.jit``: jit invokes the Python callable only
    while TRACING (cached executions skip it), so the wrapper body runs
    once per actual trace.  Each trace counts ``compile.traces`` plus
    ``counter`` (redirect-aware — warmup-pool traces land in
    ``compile.warmup_traces``, see ``instrument.trace_redirect``) and,
    when the persistent cache is installed, records its signature into
    the warmup manifest.  ``batch_argnum`` names the positional arg
    whose avals vary call-to-call (the fit step's batch dict); entries
    without one are inventory-only."""
    @functools.wraps(fn)
    def wrapper(*a, **kw):
        instrument.count_trace(counter)
        if _manifest is not None:
            _record(kind, symbol, meta, a, batch_argnum)
        return fn(*a, **kw)
    return wrapper


def _record(kind, symbol, meta, args, batch_argnum):
    # recording must never break a trace: any failure (unserializable
    # attr, deleted cache dir, odd tracer type) degrades to not-recorded
    try:
        entry = {'kind': kind,
                 'fp': fingerprint(symbol) if symbol is not None else None}
        if meta:
            entry['meta'] = jsonable(meta)
        if batch_argnum is not None:
            batch = args[batch_argnum]
            # during tracing these are jax tracers; shape/dtype read the
            # avals — exactly what a replay needs to re-lower
            entry['batch'] = {
                str(k): [[int(d) for d in v.shape], str(v.dtype)]
                for k, v in batch.items()}
        _manifest.record(entry)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Warmup pool
# ---------------------------------------------------------------------------

def _get_pool():
    global _pool
    if _pool is None:
        with _lock:
            if _pool is None:
                from concurrent.futures import ThreadPoolExecutor
                _pool = ThreadPoolExecutor(
                    max_workers=min(4, os.cpu_count() or 2),
                    thread_name_prefix='mxtpu-warmup')
    return _pool


def warmup_submit(label, build):
    """Run ``build`` (a lower+compile thunk) on the warmup pool.
    Traces it takes are redirected to ``compile.warmup_traces`` (an AOT
    pre-trace is not a hot-path retrace and must not inflate
    ``executor.xla_traces``); wall time accumulates in the
    ``compile.warmup_secs`` timer and the live count is published as
    the ``compile.warmup_inflight`` gauge; under the performance plane
    the build is a ``perf.phase.compile`` span.  Returns the Future."""
    def run():
        global _inflight
        with _lock:
            _inflight += 1
            instrument.set_gauge('compile.warmup_inflight', _inflight)
        from . import perfwatch
        t0 = time.perf_counter()
        try:
            with instrument.trace_redirect('compile.warmup_traces'):
                with instrument.span('compile.warmup[%s]' % label,
                                     cat='compile'), \
                        perfwatch.phase('compile'):
                    return build()
        finally:
            with _lock:
                _inflight -= 1
                instrument.set_gauge('compile.warmup_inflight', _inflight)
            instrument.observe('compile.warmup_secs',
                               time.perf_counter() - t0)
    return _get_pool().submit(run)


def warm_start(module, eval_metric=None, data_iter=None):
    """Entry point of ``fit(warm_start=True)``: dispatch to the
    module's ``_warm_start`` hook (Module, BucketingModule) with the
    iterator's batch signature when it exposes one.  Modules without
    the hook (custom BaseModule subclasses) warm nothing."""
    ws = getattr(module, '_warm_start', None)
    if ws is None:
        return
    ensure_persistent_cache()
    sig = None
    if data_iter is not None:
        provide_sig = getattr(data_iter, 'provide_signature', None)
        if provide_sig is not None:
            try:
                sig = provide_sig()
            except Exception:
                sig = None
    ws(eval_metric, data_sig=sig)


# ---------------------------------------------------------------------------
# Leg 3: pow2 shape policy
# ---------------------------------------------------------------------------

def pad_to_bucket(n, minimum=1):
    """Smallest power of two >= ``n`` (and >= ``minimum``): the shape
    policy that bounds the number of distinct compiled inference shapes
    to O(log max_batch) instead of one program per request size
    (counted by the ``compile.shape_buckets`` gauge)."""
    n = max(int(n), int(minimum), 1)
    return 1 << (n - 1).bit_length()


# ---------------------------------------------------------------------------
# Signature helpers (shared by Module._run_fused and _warm_start)
# ---------------------------------------------------------------------------

def sig_key(shapes_map, mesh=None):
    """Hashable key of a ``{name: (shape, dtype_str)}`` signature.
    ``mesh`` (a ``ShardingPlan.sig()`` string, or None off the sharded
    path) folds the mesh shape + partition policy into the key: the
    same batch avals compile to DIFFERENT executables per mesh, so AOT
    tables and warm-start replay must key on both."""
    key = tuple(sorted((str(k), tuple(int(d) for d in s), str(dt))
                       for k, (s, dt) in shapes_map.items()))
    if mesh is not None:
        key = key + (('__mesh__', str(mesh)),)
    return key


def batch_sig(batch, mesh=None):
    """:func:`sig_key` of a PLACED batch dict ``{name: array}`` — the
    per-step lookup key into the AOT executable table.  Delegates so
    the two key forms can never drift apart (a silent mismatch would
    turn every warm start into hot-path retraces)."""
    return sig_key({k: (v.shape, str(v.dtype))
                    for k, v in batch.items()}, mesh=mesh)
