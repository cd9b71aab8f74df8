"""Capability probes for the parallel legs."""
from __future__ import annotations

__all__ = ['multiprocess_cpu_missing']


def multiprocess_cpu_missing():
    """Why multi-process SPMD on the CPU backend is unavailable in the
    installed jaxlib, or None when it should work — the capability
    probe behind the dist_sync test skips (the PR-10 Mosaic-skip
    pattern: skip naming the missing capability, auto-unskip when an
    upgrade provides it).

    Cross-process collectives on the CPU backend arrived with the
    jaxlib collectives plugin (gloo/mpi), exposed as
    ``jaxlib.xla_client._xla.collectives``; without it every
    cross-process computation fails at runtime with
    ``Multiprocess computations aren't implemented on the CPU
    backend``.  Static attribute probe only — no backend is
    initialized and no process is forked."""
    try:
        import jaxlib
        from jaxlib.xla_client import _xla
    except Exception as exc:
        return 'jaxlib unimportable: %s: %s' % (type(exc).__name__, exc)
    if getattr(_xla, 'collectives', None) is None:
        return ('jaxlib %s lacks CPU cross-process collectives '
                '(xla_client._xla.collectives / gloo): multi-process '
                "computations aren't implemented on this CPU backend"
                % getattr(jaxlib, '__version__', '?'))
    return None
