"""Fault-tolerance primitives — retry policy, atomic file commits,
fault injection.

The reference stack inherited its recovery machinery from ps-lite: van
reconnect with exponential backoff (``ps-lite/src/van.cc``), heartbeat
timeouts (``kvstore_dist.h:151-160`` ``get_num_dead_node``), and resumable
checkpoints driven by ``--load-epoch``.  This module is the TPU-native
home of those mechanics, consumed by :mod:`mxnet_tpu.kvstore_server`
(RPC retry/reconnect + replay), :mod:`mxnet_tpu.model` (atomic
checkpoint commit + validity-checked resume) and the chaos tests.

Three pieces:

- :class:`RetryPolicy` — exponential backoff with seeded jitter, a cap,
  an optional attempt budget and a wall-clock deadline.  Deterministic
  under a fixed seed so backoff/jitter math is unit-testable.
- :func:`atomic_replace` — write-tmp + fsync + ``os.replace`` + dir
  fsync commit for checkpoints and server state: a ``kill -9`` at any
  instant leaves either the old file or the new file, never a torn one.
- Fault injection — ``MXTPU_FAULTS`` describes frame drops, delays,
  severed connections and process kills at named points inside the
  kvstore transport; :func:`fault_point` is called from those sites and
  is a single flag check when no plan is armed (the same off-path
  discipline as :mod:`mxnet_tpu.instrument`, pinned by
  ``tests/test_resilience.py``).

``MXTPU_FAULTS`` grammar (semicolon-separated directives)::

    site:action[:arg[:arg2]]

    site    prefix-matched against the firing point name; points are
            'client.send.<op>', 'client.recv.<op>', 'server.recv.<op>',
            'server.apply', 'server.barrier' — so 'client.send.push'
            targets pushes only, 'client.send' every outbound frame.
            The serving fleet adds 'serve.execute.r<id>',
            'serve.flush.r<id>' and 'serve.worker.r<id>' (one per
            replica; docs/resilience.md lists them all).
    action  drop:P        drop the frame with probability P
            delay:P:SECS  sleep SECS with probability P
            sever:P       raise ConnectionResetError with probability P
            wedge:P:SECS  sleep SECS with probability P — same mechanics
                          as delay, but named for what it simulates: a
                          WEDGED worker holding its flush (the serving
                          supervisor's quarantine drill)
            after:N:ACT   fire ACT ('drop'|'sever'|'kill') deterministically
                          on the Nth matching event (1-based), once;
                          'after:N:wedge:SECS' wedges SECS once
            kill:P        SIGKILL the current process (chaos harness
                          use).  At sites fired with
                          ``fault_point(..., thread_kill=True)`` (the
                          serving worker loop) 'kill' raises
                          :class:`InjectedDeath` instead: the WORKER is
                          the unit of failure there, and the process
                          must survive to supervise its replacement.

Example: ``MXTPU_FAULTS='client.send.push:drop:0.2;server.barrier:after:2:kill'``
with ``MXTPU_FAULTS_SEED`` pinning the coin flips.
"""
from __future__ import annotations

import contextlib
import os
import random
import signal
import tempfile
import threading
import time

from . import config
# fs and iowatch are jax-free: importing them here keeps this module
# usable from a process that must stay backend-free
from . import fs
from . import iowatch

__all__ = [
    'RetryPolicy', 'atomic_replace',
    'faults_on', 'fault_point', 'set_faults', 'clear_faults', 'FaultPlan',
    'InjectedFault', 'InjectedDeath', 'on_kill',
]


# ---------------------------------------------------------------------------
# Retry policy
# ---------------------------------------------------------------------------

class RetryPolicy(object):
    """Exponential backoff with jitter and a per-op deadline.

    ``delay(attempt)`` for attempt 0,1,2,... is
    ``min(base * multiplier**attempt, max_delay)`` scaled by a uniform
    jitter factor in ``[1, 1+jitter]``.  Seedable so tests can pin the
    exact sleep sequence.
    """

    __slots__ = ('base', 'multiplier', 'max_delay', 'jitter',
                 'deadline', 'max_retries', '_rng')

    def __init__(self, base=0.05, multiplier=2.0, max_delay=2.0,
                 jitter=0.25, deadline=120.0, max_retries=None, seed=None):
        assert base >= 0 and multiplier >= 1.0 and max_delay >= base
        assert jitter >= 0
        self.base = float(base)
        self.multiplier = float(multiplier)
        self.max_delay = float(max_delay)
        self.jitter = float(jitter)
        self.deadline = float(deadline)
        self.max_retries = max_retries
        self._rng = random.Random(seed)

    @classmethod
    def from_env(cls, seed=None):
        """Build from the ``MXTPU_KV_RETRY_*`` / ``MXTPU_KV_OP_DEADLINE``
        knobs (:mod:`mxnet_tpu.config`)."""
        return cls(base=config.get('MXTPU_KV_RETRY_BASE'),
                   max_delay=config.get('MXTPU_KV_RETRY_MAX'),
                   jitter=config.get('MXTPU_KV_RETRY_JITTER'),
                   deadline=config.get('MXTPU_KV_OP_DEADLINE'),
                   seed=seed)

    def delay(self, attempt):
        """Backoff before retry number ``attempt`` (0-based)."""
        d = min(self.base * (self.multiplier ** attempt), self.max_delay)
        if self.jitter:
            d *= 1.0 + self._rng.uniform(0.0, self.jitter)
        return d

    def run(self, fn, retry_on=(OSError,), deadline=None, on_retry=None):
        """Call ``fn`` until it returns, raising when the attempt budget
        or the wall-clock deadline (seconds, default ``self.deadline``)
        would be exceeded by the next backoff sleep.  ``on_retry(attempt,
        exc)`` observes each retry (metrics hooks)."""
        t_end = time.monotonic() + (self.deadline if deadline is None
                                    else deadline)
        attempt = 0
        while True:
            try:
                return fn()
            except retry_on as e:
                if (self.max_retries is not None
                        and attempt >= self.max_retries):
                    raise
                d = self.delay(attempt)
                if time.monotonic() + d >= t_end:
                    raise
                if on_retry is not None:
                    on_retry(attempt, e)
                # backoff sleeps on the fit thread are recovery badput
                # (the goodput ledger's 'recovery' bucket); from any
                # other thread account() is the shared no-op
                with iowatch.account('recovery'):
                    time.sleep(d)
                attempt += 1


# ---------------------------------------------------------------------------
# Atomic file commit
# ---------------------------------------------------------------------------

_umask_cache = None
_umask_lock = threading.Lock()


def _process_umask():
    """The process umask, probed ONCE under a lock and cached.  The
    probe (os.umask(0) + restore) is process-global: two concurrent
    un-serialized probes can interleave so one 'restores' the other's
    temporary 0 and every later file becomes world-writable."""
    global _umask_cache
    if _umask_cache is None:
        with _umask_lock:
            if _umask_cache is None:
                cur = os.umask(0)
                os.umask(cur)
                _umask_cache = cur
    return _umask_cache


@contextlib.contextmanager
def atomic_replace(path):
    """Yield a temp path in ``path``'s directory; on clean exit fsync it,
    ``os.replace`` it over ``path`` and fsync the directory — the
    checkpoint either fully commits or the previous file survives intact
    (``kill -9`` mid-write leaves only a ``.tmp.*`` orphan, never a
    truncated ``path``).  Remote URIs pass through unchanged: fsspec
    writers upload whole objects at close, the spool model of the
    reference's S3 WriteStream."""
    if fs.is_remote(path):
        yield path
        return
    if path.startswith('file://'):
        path = path[len('file://'):]
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=d,
                               prefix=os.path.basename(path) + '.tmp.')
    os.close(fd)
    # mkstemp creates 0600; os.replace would silently propagate that
    # onto checkpoints other users/services must read.  Preserve the
    # target's existing mode, or fall back to the umask default.
    try:
        mode = os.stat(path).st_mode & 0o7777
    except OSError:
        mode = 0o666 & ~_process_umask()
    try:
        os.chmod(tmp, mode)
    except OSError:
        pass
    try:
        yield tmp
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, path)
        dfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------

class InjectedFault(ConnectionResetError):
    """A connection failure manufactured by the fault plan (subclass of
    the real error so recovery paths cannot tell it apart)."""


class InjectedDeath(RuntimeError):
    """A ``kill`` directive fired at a site whose caller declared
    ``thread_kill=True``: the calling WORKER (a serving replica's
    coalescing thread) must treat this as its own unhandled death —
    the process survives, so the supervisor can observe the dead
    worker and replace it."""


class _Directive(object):
    __slots__ = ('site', 'action', 'prob', 'arg', 'arg2', 'count',
                 'fired')

    def __init__(self, site, action, prob, arg, arg2=None):
        self.site = site
        self.action = action      # drop | delay | wedge | sever | kill | after
        self.prob = prob
        self.arg = arg            # delay/wedge seconds / after-sub-action
        self.arg2 = arg2          # after:N:wedge's seconds
        self.count = 0            # matching events seen (for 'after')
        self.fired = False


class FaultPlan(object):
    """Parsed ``MXTPU_FAULTS`` spec; one shared seeded RNG, all state
    under a lock (faults only run in chaos tests — contention is not a
    concern, determinism is)."""

    def __init__(self, spec, seed=0):
        self.spec = spec
        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self._directives = []
        for tok in spec.split(';'):
            tok = tok.strip()
            if not tok:
                continue
            parts = tok.split(':')
            if len(parts) < 2:
                raise ValueError('bad MXTPU_FAULTS directive %r '
                                 '(want site:action[:arg])' % tok)
            site, action = parts[0], parts[1]
            if action == 'after':
                # site:after:N:subaction — 'wedge' alone takes seconds
                if len(parts) == 5 and parts[3] == 'wedge':
                    self._directives.append(
                        _Directive(site, 'after', float(parts[2]),
                                   'wedge', float(parts[4])))
                    continue
                if len(parts) != 4 or parts[3] not in ('drop', 'sever',
                                                       'kill'):
                    raise ValueError(
                        'bad after-directive %r (want site:after:N:'
                        'drop|sever|kill or site:after:N:wedge:SECS)'
                        % tok)
                self._directives.append(
                    _Directive(site, 'after', float(parts[2]), parts[3]))
            elif action in ('drop', 'sever', 'kill'):
                prob = float(parts[2]) if len(parts) > 2 else 1.0
                self._directives.append(_Directive(site, action, prob, None))
            elif action in ('delay', 'wedge'):
                if len(parts) < 4:
                    raise ValueError('bad %s-directive %r '
                                     '(want site:%s:P:SECS)'
                                     % (action, tok, action))
                self._directives.append(
                    _Directive(site, action, float(parts[2]),
                               float(parts[3])))
            else:
                raise ValueError('unknown fault action %r in %r'
                                 % (action, tok))

    def fire(self, point, thread_kill=False):
        """Evaluate every directive matching ``point`` (prefix match).
        Returns 'drop' when the frame should be discarded; may sleep;
        may raise :class:`InjectedFault`; may SIGKILL the process.
        ``thread_kill=True`` (the serving worker loop) turns a 'kill'
        into a raised :class:`InjectedDeath` — the worker dies, the
        process survives.  Actions are DECIDED under the lock
        (deterministic RNG) but EXECUTED outside it — a delay that
        slept while holding the lock would serialize every other
        thread's fault points with it, distorting the very scenario
        the plan describes."""
        result = None
        delays = []
        hard = None            # 'sever' | 'kill'
        with self._lock:
            for d in self._directives:
                if not point.startswith(d.site):
                    continue
                if d.action == 'after':
                    d.count += 1
                    if d.fired or d.count != int(d.prob):
                        continue
                    d.fired = True
                    act = d.arg
                elif self._rng.random() < d.prob:
                    act = d.action
                else:
                    continue
                if act == 'drop':
                    result = 'drop'
                elif act in ('delay', 'wedge'):
                    delays.append(d.arg if d.action != 'after'
                                  else d.arg2)
                else:
                    hard = act
        for seconds in delays:
            time.sleep(seconds)
        if hard == 'sever':
            raise InjectedFault('injected fault: sever at %s' % point)
        if hard == 'kill' and thread_kill:
            raise InjectedDeath('injected fault: worker kill at %s'
                                % point)
        if hard == 'kill':
            # last-breath hooks (the health flight recorder dumps its
            # postmortem here): SIGKILL is uncatchable, so this is the
            # only instant a record of the injected death can be written
            for fn in list(_kill_hooks):
                try:
                    fn()
                except Exception:
                    pass
            os.kill(os.getpid(), signal.SIGKILL)
        return result


_plan = None          # armed FaultPlan, or None (the common case)
_kill_hooks = []      # run just before an injected SIGKILL


def on_kill(fn):
    """Register ``fn`` to run immediately before a MXTPU_FAULTS-injected
    ``kill`` fires (idempotent).  Hooks must be best-effort and fast —
    the process is about to SIGKILL itself."""
    if fn not in _kill_hooks:
        _kill_hooks.append(fn)


def faults_on():
    """Single cheap check for transport hot paths."""
    return _plan is not None


def fault_point(site, op=None, thread_kill=False):
    """Fire the armed fault plan at ``site`` (plus ``.op`` when given).
    Returns 'drop' to ask the caller to discard the frame; may sleep,
    raise :class:`InjectedFault`, or kill the process.
    ``thread_kill=True`` declares the calling WORKER the unit of
    failure: a 'kill' directive raises :class:`InjectedDeath` (the
    worker dies, the process survives) instead of SIGKILL.  No plan
    armed: returns immediately."""
    plan = _plan
    if plan is None:
        return None
    return plan.fire(site if op is None else '%s.%s' % (site, op),
                     thread_kill=thread_kill)


def set_faults(spec, seed=None):
    """Arm (or, with a falsy spec, disarm) a fault plan at runtime.
    Arming — and disarming an actually-armed plan — is a typed
    ``faults`` decision event, so an injected chaos run reads causally
    on the chronicle timeline: the arm precedes the anomalies it
    causes.  (Import-time refresh with no knob set emits nothing.)"""
    global _plan
    from . import instrument
    if not spec:
        if _plan is not None:
            _plan = None
            instrument.decision('faults', 'clear',
                                reason='fault plan disarmed')
        return None
    _plan = FaultPlan(spec, seed=config.get('MXTPU_FAULTS_SEED')
                      if seed is None else seed)
    instrument.decision('faults', 'arm', severity='warn',
                        reason='fault plan armed: %s' % (spec,),
                        spec=str(spec))
    return _plan


def clear_faults():
    set_faults(None)


def _refresh_from_env():
    set_faults(config.get('MXTPU_FAULTS'))


_refresh_from_env()
