"""Production serving fleet — a dynamic-batching model server on the
Predictor/AOT substrate (docs/serving.md).

The TensorFlow paper (1605.08695) treats serving as a first-class
deployment mode of the same graph runtime; this package is that play
here: the request loop lives in front of the SAME pow2-bucketed,
AOT-cached executor stack ``Module``/``Predictor`` already use, so a
model served hot shares every compile-cache and instrument investment
the trainer made — including the PR-8 NamedSharding rails
(``load_model(mesh='dp=1,tp=2')`` serves each replica tensor-parallel
over its own disjoint device set).

- :class:`ModelServer` — named-model registry (hot load/unload/reload),
  N replicas per model behind one shared admission queue with
  per-replica :class:`DynamicBatcher` workers (coalesce to pow2
  buckets, flush on ``MXTPU_SERVE_MAX_DELAY_MS``), priority lanes
  (``priority='interactive'`` preempts batch coalescing at flush
  boundaries), admission control (``MXTPU_SERVE_MAX_QUEUE`` per lane →
  :class:`ServerOverloadedError`), and p50/p95/p99
  queue-wait/execute/e2e histograms — model-wide plus labeled
  per-replica/per-lane series — in the instrument registry
  (``instrument.render_prometheus`` exports the labels).
- :class:`ReplicaAutoscaler` — closed-loop controller holding the
  WINDOWED p99 at the SLO: scales replicas up/down and shrinks/
  restores the max batch with hysteresis, every decision logged as an
  event (``server.autoscale(name, slo_p99_ms=...)``); with
  ``MXTPU_SERVE_BROWNOUT`` it degrades gracefully at capacity (shed
  batch lane -> shrink batch -> smallest bucket) before interactive
  traffic sheds.
- :class:`FleetSupervisor` — the fleet's detect→repair loop
  (``server.supervise(name)`` / ``MXTPU_SERVE_SUPERVISE``): a replica
  wedged past ``MXTPU_SERVE_WEDGE_MS`` or dead on an exception is
  quarantined, its in-flight requests replayed once at their lane's
  head (:class:`ReplicaQuarantinedError` on the second displacement),
  and a warmed replacement attached before the tear-down.  Request
  deadlines (``submit(deadline_ms=...)`` /
  ``MXTPU_SERVE_DEADLINE_MS``) bound every wait with a typed
  :class:`DeadlineExceededError`, dropped at coalesce time — never
  executed dead (docs/serving.md "Failure semantics").
- ``tools/serve_bench.py`` — open-/closed-loop load generator and the
  fleet's offline calibrator.
- ``tools/check_serving.py`` / ``tools/check_fleet.py`` — end-to-end
  smokes (coalescing, bit-exact responses, shedding, hot reload; tp=2
  oracle parity, replica scaling, autoscale-on-load-step, priority
  preemption, and the traced request-attribution leg).
- :mod:`mxnet_tpu.serving.servewatch` — the request-attribution plane
  (``MXTPU_SERVEWATCH``): per-request span chains with exclusive
  buckets summing to e2e, flush composition records, histogram
  exemplars, and durable tail postmortems (docs/serving.md).

Importing this package starts nothing: threads exist only per
constructed server, and with metrics off every instrument call is a
single flag check.
"""
from . import servewatch
from .autoscaler import ReplicaAutoscaler
from .batcher import (DeadlineExceededError, DynamicBatcher,
                      ReplicaQuarantinedError, ServerOverloadedError,
                      LANE_BATCH, LANE_INTERACTIVE)
from .server import ModelNotFoundError, ModelServer
from .supervisor import FleetSupervisor

__all__ = ['ModelServer', 'DynamicBatcher', 'ServerOverloadedError',
           'DeadlineExceededError', 'ReplicaQuarantinedError',
           'ModelNotFoundError', 'ReplicaAutoscaler',
           'FleetSupervisor', 'servewatch',
           'LANE_BATCH', 'LANE_INTERACTIVE']
