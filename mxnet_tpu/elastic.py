"""Elastic self-healing plane — the repair half of the detect→repair loop.

PR 2 made the transport survive faults, PR 5's heartbeat plane marks
ranks dead, and PRs 9/10 name stragglers and price recovery seconds in
the goodput ledger — but nothing *acted*: a dead worker degraded the job
until a human restarted it.  The parameter-server lineage treats worker
churn as a normal operating condition (MXNet, 1512.01274) and
TensorFlow makes fault recovery a mode of the same runtime
(1605.08695); this module closes the loop on top of the kv server's
elastic membership epoch (``kvstore_server.py``: dead-rank eviction,
generation numbers, the ``join``/``membership``/``resize``/``ckpt_vote``
RPCs):

- **Coordinator** (:class:`ElasticCoordinator`): one per fit, armed by
  ``MXTPU_ELASTIC`` (or by being a joiner).  A daemon thread polls the
  server's membership view every ``MXTPU_ELASTIC_POLL`` seconds —
  reporting this rank's epoch progress on the same RPC — and flags
  repairs; the FIT THREAD executes them (via :func:`step_check`, one
  global None check per batch when off) so every repair second lands in
  the goodput ledger's ``recovery`` bucket.
- **Repair rendezvous**: when a rank is evicted, survivors hold the
  vacancy open for ``MXTPU_ELASTIC_WAIT`` seconds.  A replacement
  joining resolves it (training resumes at full width); otherwise the
  survivors commit a cluster shrink via the idempotent generation-gated
  ``resize`` RPC — and a module fitting on a device mesh additionally
  rebuilds it with ``dp`` reduced (``Module._apply_dp_shrink``:
  re-derived FitShardings/ZeRO placements, re-AOT through the
  warm-start pool) — training continues at reduced throughput instead
  of stalling.
- **Joiner re-seed** (:func:`seed_joiner`): a replacement worker
  (``MXTPU_ELASTIC_JOIN=1``) bootstraps from the cross-rank checkpoint
  consensus (``model.consensus_latest_checkpoint`` — a rank that died
  mid-save cannot make peers resume from an epoch it never committed)
  plus a live-store param pull, then enters the fit loop at the
  cluster's current epoch without a global restart.
- **Health actuation**: a cluster health verdict raised by the server
  (one rank's sentinels saw bad steps under
  ``MXTPU_HEALTH_ACTION=skip_update``/``abort``) propagates through the
  membership poll; every rank flight-records it, and ``abort``
  raises a coordinated :class:`health.TrainingDivergedError` on the fit
  thread — a clean cluster-wide stop, not a hang.

Everything is off by default and costs one module-global None check per
batch when off (the instrument/iowatch discipline).  See
docs/resilience.md "elastic membership & repair".
"""
from __future__ import annotations

import logging
import threading
import time

from . import config
from . import instrument
from . import iowatch

__all__ = [
    'ElasticCoordinator', 'activate_fit', 'deactivate_fit',
    'active_coordinator', 'step_check', 'note_checkpoint',
    'seed_joiner', 'reconcile_resume',
]


class ElasticCoordinator(object):
    """One fit's repair loop against one control-plane kv store (any
    object speaking ``membership``/``resize``/``ckpt_vote`` — the
    ``DistAsyncKVStore`` passthroughs, or a raw ``AsyncKVClient`` in
    tests).  The poll thread only OBSERVES and flags; all repairs run
    on the fit thread inside :meth:`step` so the goodput ledger's
    ``recovery`` bucket prices them."""

    def __init__(self, kv, wait=None, poll=None):
        self._kv = kv
        self._wait = float(config.get('MXTPU_ELASTIC_WAIT')
                           if wait is None else wait)
        self._poll = max(0.05, float(config.get('MXTPU_ELASTIC_POLL')
                                     if poll is None else poll))
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._epoch = None            # last epoch the fit loop reported
        self._generation = None
        self._event_gen = None        # newest membership event processed
        self._peer_resize = False     # a peer committed the shrink
        self._fenced = False
        self._alert = None            # unhandled cluster health verdict
        self._alert_handled = 0       # highest alert id already acted on
        self._repair_t0 = None        # monotonic time an evict surfaced
        self._await_step = False      # repair done; stamp next step
        self._thread = None

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._poll_loop, daemon=True,
                name='mxtpu-elastic-poll')
            self._thread.start()
        return self

    def stop(self):
        self._stop.set()

    # -- poll thread: observe + flag ---------------------------------------
    def _poll_loop(self):
        while not self._stop.is_set():
            try:
                view = self._kv.membership(self._epoch)
            except Exception:
                # the transport has its own recovery story; a poll that
                # could not reach the server says nothing about
                # membership
                view = None
            if view is not None:
                self._ingest(view)
            self._stop.wait(self._poll)

    def _ingest(self, view):
        """Fold one membership view into the coordinator state (poll
        thread or fit thread — both call it).  Repairs are detected
        from the server's generation-tagged membership EVENTS, not the
        instantaneous vacancy view: a replacement's join can claim a
        vacancy atomically with the sweep that opened it, so a slow
        poller would otherwise never see the eviction at all.  Events
        at or below the generation of this coordinator's FIRST view
        are history (a joiner must not replay the eviction that
        created its own seat)."""
        with self._lock:
            gen = int(view.get('generation', 0))
            first = self._generation is None
            if first:
                self._generation = gen
                self._event_gen = gen      # older events are history
                # ... and so is a verdict raised before this fit: the
                # abort belonged to the previous fit's era
                stale = view.get('health')
                if stale:
                    self._alert_handled = max(self._alert_handled,
                                              int(stale.get('id', 0)))
            elif gen != self._generation:
                self._generation = gen
                instrument.inc('elastic.generation_changes')
                instrument.decision('elastic', 'generation',
                                    reason='membership generation '
                                           'changed', generation=gen)
            instrument.set_gauge('elastic.generation', float(gen))
            if view.get('fenced'):
                self._fenced = True
            news = [e for e in (view.get('events') or ())
                    if int(e.get('generation', 0)) > self._event_gen]
            if news:
                self._event_gen = max(int(e['generation'])
                                      for e in news)
            evicts = [e for e in news if e.get('kind') == 'evict']
            # the first view marks resolved history, but a vacancy
            # STILL OPEN in it is an unresolved repair by definition —
            # a rank that died before this coordinator's first poll
            # (even the poll whose sweep evicted it) must not be
            # silently ignored.  Also the fallback for pre-events
            # servers.
            if not evicts and (first or view.get('events') is None) \
                    and (view.get('vacant') or {}):
                evicts = [{'rank': r} for r in view['vacant']]
            if evicts and self._repair_t0 is None:
                self._repair_t0 = time.monotonic()
                instrument.inc('elastic.evictions_observed',
                               len(evicts))
                instrument.decision(
                    'elastic', 'evict_observed', severity='warn',
                    reason='rank(s) %s evicted at generation %d'
                           % (sorted(e.get('rank') for e in evicts),
                              gen),
                    generation=gen)
                logging.warning(
                    'mxtpu elastic: rank(s) %s evicted at generation '
                    '%d — holding the vacancy for a replacement up to '
                    '%.1fs', sorted(e.get('rank') for e in evicts),
                    gen, self._wait)
            if any(e.get('kind') == 'resize' for e in news):
                self._peer_resize = True
            alert = view.get('health')
            if alert and int(alert.get('id', 0)) > self._alert_handled:
                self._alert = alert
        return view

    # -- fit thread: act ---------------------------------------------------
    def step(self, module=None, epoch=None):
        """Per-batch actuation hook (the body behind
        :func:`step_check`).  Raises on a fenced identity or a cluster
        abort verdict; runs the repair rendezvous when a vacancy is
        open; stamps the first post-repair productive step."""
        if epoch is not None:
            self._epoch = int(epoch)
        with self._lock:
            fenced = self._fenced
            alert = self._alert
            repairing = self._repair_t0 is not None
            stamp = self._await_step
            if stamp:
                self._await_step = False
        if stamp:
            # the previous step() resolved a repair and a batch has
            # been dispatched since — this is the post-repair
            # productive step tools/check_elastic.py times recovery to
            instrument.set_gauge('elastic.post_repair_step_at',
                                 time.time())
        if fenced:
            self._reclaim_or_die()
        if alert is not None:
            self._act_on_alert(alert)
        if repairing:
            with iowatch.account('recovery'):
                self._rendezvous(module)

    def _act_on_alert(self, alert):
        from . import health as _health
        with self._lock:
            if int(alert.get('id', 0)) <= self._alert_handled:
                return
            self._alert_handled = int(alert.get('id', 0))
            self._alert = None
        if _health.note_cluster_alert(alert):
            raise _health.cluster_diverged_error(alert)

    def _reclaim_or_die(self):
        """This client was evicted (a transient stall read as death).
        Its seat may still be vacant — one join attempt reclaims it
        (the server un-fences a joiner); otherwise the rank belongs to
        a replacement now and this process must fail fast, not corrupt
        its successor's training."""
        from .kvstore_server import StaleGenerationError
        join = getattr(self._kv, 'rejoin', None) or \
            getattr(self._kv, 'join', None)
        if join is not None:
            try:
                with iowatch.account('recovery'):
                    info = join(timeout=self._wait)
            except ConnectionError as e:
                if 'no vacancy' not in str(e):
                    # transport failure, not a verdict on the seat:
                    # surface the REAL error (the fit's transport
                    # recovery owns it), never a fabricated
                    # "replacement owns the seat" postmortem
                    raise
            else:
                with self._lock:
                    self._fenced = False
                instrument.inc('elastic.seat_reclaims')
                instrument.decision(
                    'elastic', 'seat_reclaim', severity='warn',
                    reason='transiently evicted; reclaimed rank %s at '
                           'generation %s'
                           % (info.get('rank'), info.get('generation')),
                    rank=info.get('rank'))
                logging.warning(
                    'mxtpu elastic: this worker was transiently evicted '
                    'and reclaimed rank %s at generation %s',
                    info.get('rank'), info.get('generation'))
                return
        raise StaleGenerationError(
            'this worker was evicted and no vacancy remains — a '
            'replacement owns the seat (or the cluster shrank past '
            'it); this process must not keep writing')

    def _rendezvous(self, module):
        """Hold for the repair decision: a replacement join fills the
        vacancy (full-width resume), or the MXTPU_ELASTIC_WAIT deadline
        commits the generation-gated shrink.  Runs on the fit thread
        under the goodput ledger's ``recovery`` bucket — the window
        this prices IS the recovery the ledger reports."""
        t0 = time.monotonic()
        mode = None
        # bounded: when the server itself becomes unreachable the
        # repair loop must surface the transport error like any other
        # op would (the PR-2 contract), not spin the fit thread
        # forever inside step_check
        dead_after = float(config.get('MXTPU_KV_RECONNECT_DEADLINE'))
        t_give_up = time.monotonic() + dead_after
        while not self._stop.is_set():
            try:
                view = self._kv.membership(self._epoch)
            except Exception:
                if time.monotonic() >= t_give_up:
                    raise
                time.sleep(self._poll)
                continue
            t_give_up = time.monotonic() + dead_after
            self._ingest(view)
            with self._lock:
                if self._fenced:
                    break
                peer_resized = self._peer_resize
            vacant = view.get('vacant') or {}
            if not vacant:
                # the vacancy is gone: a replacement claimed it, or a
                # peer survivor already committed the shrink
                mode = 'shrink' if peer_resized else 'replacement'
                break
            if max(vacant.values()) >= self._wait:
                from .kvstore_server import StaleGenerationError
                # shrink by the EXPIRED vacancies only: a younger
                # vacancy keeps its full replacement-hold window (the
                # server retires oldest-first, exactly this set)
                expired = [r for r, age in vacant.items()
                           if age >= self._wait]
                target = max(1, int(view.get('num_workers', 1))
                             - len(expired))
                try:
                    # gated on the generation this DECISION saw: a
                    # replacement joining in the window rejects the
                    # commit and the re-poll resolves by replacement
                    gen, n = self._kv.resize(
                        target, view.get('generation'))
                except StaleGenerationError:
                    continue
                instrument.inc('elastic.shrinks')
                instrument.decision(
                    'elastic', 'shrink', severity='warn',
                    reason='no replacement within %.1fs — cluster '
                           'shrunk to %d worker(s) at generation %d'
                           % (self._wait, n, gen),
                    workers=n, generation=gen)
                logging.warning(
                    'mxtpu elastic: no replacement within %.1fs — '
                    'cluster shrunk to %d worker(s) at generation %d',
                    self._wait, n, gen)
                if len(expired) == len(vacant):
                    mode = 'shrink'
                    break
                continue    # a younger vacancy keeps its own window
            time.sleep(self._poll)
        if mode == 'shrink' and module is not None:
            # a mesh-active fit additionally rebuilds its mesh one dp
            # narrower (re-derived shardings, warm re-AOT) — every
            # survivor applies it, not only the resize proposer
            shrink = getattr(module, '_apply_dp_shrink', None)
            if shrink is not None:
                shrink()
        with self._lock:
            self._repair_t0, t_detect = None, self._repair_t0
            self._peer_resize = False
            fenced = self._fenced
            self._await_step = mode is not None
        if fenced:
            self._reclaim_or_die()
        if mode is None:
            return
        dt = time.monotonic() - (t_detect if t_detect is not None else t0)
        instrument.inc('elastic.repairs')
        instrument.decision('elastic', 'repaired',
                            reason='repaired by %s after %.2fs'
                                   % (mode, dt),
                            mode=mode, recovery_secs=dt)
        instrument.set_gauge('elastic.recovery_secs', dt)
        instrument.set_gauge('elastic.repaired_at', time.time())
        logging.warning(
            'mxtpu elastic: repaired by %s after %.2fs — training '
            'resumes', mode, dt)

    # -- checkpoint consensus feed -----------------------------------------
    def vote_checkpoints(self, prefix):
        """Report this rank's loadable checkpoint epochs to the server
        (called after every checkpoint commit) so a joiner's consensus
        is computed against CURRENT votes, not stale ones."""
        from . import model as _model
        try:
            self._kv.ckpt_vote(_model.loadable_epochs(prefix))
        except Exception:
            logging.warning('mxtpu elastic: ckpt_vote failed',
                            exc_info=True)


# ---------------------------------------------------------------------------
# Per-fit activation (one coordinator; the BaseModule.fit token pattern)
# ---------------------------------------------------------------------------

_coord = None
_coord_lock = threading.Lock()


def _kv_speaks_membership(kv):
    return kv is not None and hasattr(kv, 'membership') and \
        hasattr(kv, 'resize')


def activate_fit(module, kv):
    """Called by ``BaseModule.fit`` after ``init_optimizer``: arm the
    coordinator when the plane is on (``MXTPU_ELASTIC``, or this worker
    is a joiner) and the store speaks the membership protocol.  Returns
    the coordinator this fit OWNS (its token for
    :func:`deactivate_fit`), or None — a nested/concurrent fit must not
    clobber the outer fit's coordinator."""
    global _coord
    if not _kv_speaks_membership(kv):
        return None
    if not (config.get('MXTPU_ELASTIC')
            or getattr(kv, 'elastic_join_info', None) is not None):
        return None
    with _coord_lock:
        if _coord is not None:
            return None
        _coord = ElasticCoordinator(kv).start()
        return _coord


def deactivate_fit(token):
    """Stop + clear the coordinator IFF ``token`` owns it (the fit
    that activated; None no-ops)."""
    global _coord
    if token is None:
        return
    with _coord_lock:
        if _coord is token:
            _coord = None
    token.stop()


def active_coordinator():
    return _coord


def step_check(module, epoch=None):
    """Per-batch hook in the fit loop: one global None check when the
    plane is off.  May raise (coordinated abort, fenced identity) or
    block briefly (the repair rendezvous, charged to ``recovery``)."""
    coord = _coord
    if coord is None:
        return
    coord.step(module, epoch)


def note_checkpoint(prefix):
    """The fit loop committed a checkpoint: refresh this rank's ckpt
    vote so the consensus is current."""
    coord = _coord
    if coord is not None:
        coord.vote_checkpoints(prefix)


def reconcile_resume(module, kv, checkpoint_prefix, begin_epoch):
    """Reconcile a SINGLE-RANK auto-resume decision with the
    cross-rank checkpoint consensus (``BaseModule.fit`` calls this
    after ``init_optimizer`` when the plane is armed and the local
    ``find_latest_checkpoint`` resumed): a rank killed mid-save holds
    one epoch fewer than its peers, and every rank training from its
    own newest epoch would push gradients computed at DIVERGENT
    parameter eras into the same store.  When the consensus epoch is
    older than the local pick, reload it and return it; otherwise
    return ``begin_epoch`` unchanged (best effort: an unreachable
    consensus keeps the local decision rather than blocking the
    restart)."""
    if begin_epoch <= 0 or not checkpoint_prefix or kv is None or \
            not hasattr(kv, 'ckpt_vote'):
        return begin_epoch
    from . import model as _model
    try:
        epoch = _model.consensus_latest_checkpoint(checkpoint_prefix,
                                                   kv=kv)
    except Exception:
        logging.warning('mxtpu elastic: checkpoint consensus '
                        'unreachable; keeping the local auto-resume '
                        'epoch %d', begin_epoch, exc_info=True)
        return begin_epoch
    if epoch is None or epoch >= begin_epoch:
        return begin_epoch
    try:
        _, arg_p, aux_p = _model.load_checkpoint(checkpoint_prefix,
                                                 epoch)
        module.set_params(arg_p, aux_p, force_init=True)
    except Exception:
        logging.warning('mxtpu elastic: consensus epoch %d unloadable '
                        'here; keeping the local auto-resume epoch %d',
                        epoch, begin_epoch, exc_info=True)
        return begin_epoch
    instrument.inc('elastic.consensus_downgrades')
    logging.warning(
        'mxtpu elastic: auto-resume downgraded from local epoch %d to '
        'the cross-rank consensus epoch %d — not every live rank '
        'committed the newer checkpoint(s)', begin_epoch, epoch)
    return epoch


# ---------------------------------------------------------------------------
# Joiner re-seed
# ---------------------------------------------------------------------------

def seed_joiner(module, kv, checkpoint_prefix, begin_epoch):
    """Bootstrap a replacement worker mid-job (``BaseModule.fit`` calls
    this after ``init_optimizer`` when the store joined): restore
    params from the cross-rank checkpoint consensus, overlay the live
    store's CURRENT params (the master copy beats any checkpoint), and
    return the epoch to enter the fit loop at — the cluster's current
    one, so the joiner trains alongside the survivors instead of
    replaying the whole job.  Returns ``begin_epoch`` unchanged for
    non-joiners."""
    info = getattr(kv, 'elastic_join_info', None) if kv is not None \
        else None
    if info is None:
        return begin_epoch
    target = int(begin_epoch)
    if checkpoint_prefix:
        from . import model as _model
        epoch = _model.consensus_latest_checkpoint(checkpoint_prefix,
                                                   kv=kv)
        if epoch is not None and epoch > target:
            try:
                _, arg_p, aux_p = _model.load_checkpoint(
                    checkpoint_prefix, epoch)
                module.set_params(arg_p, aux_p, allow_missing=False,
                                  force_init=True)
                target = epoch
                instrument.inc('elastic.joiner_ckpt_reseeds')
            except Exception:
                logging.warning(
                    'mxtpu elastic: consensus checkpoint %s-%04d '
                    'unloadable here; falling back to the live store',
                    checkpoint_prefix, epoch, exc_info=True)
    pull = getattr(module, '_elastic_pull_params', None)
    if pull is not None and pull():
        instrument.inc('elastic.joiner_live_pulls')
    cluster_epoch = int((info.get('topology') or {})
                        .get('cluster_epoch', -1))
    try:
        view = kv.membership()
        cluster_epoch = max(cluster_epoch,
                            int(view.get('cluster_epoch', -1)))
    except Exception:
        pass
    if cluster_epoch > target:
        target = cluster_epoch
    logging.warning(
        'mxtpu elastic: joined as rank %s at generation %s — entering '
        'the fit loop at epoch %d (cluster epoch %d)',
        info.get('rank'), info.get('generation'), target, cluster_epoch)
    return target
