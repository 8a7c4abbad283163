"""Opt-in ``jax.profiler`` capture and block-until-ready wall-time scopes.

Two layers, both no-ops unless explicitly started:

  * :func:`start` / :func:`stop` wrap ``jax.profiler.start_trace`` /
    ``stop_trace`` so a CLI flag can capture a device profile into a
    directory (view with TensorBoard or xprof).  Failures to start (e.g.
    a platform without profiler support) downgrade to a warning -- the
    modeled telemetry must never die because the measured layer can't
    attach.
  * :func:`wall` -- a context manager that times a host scope with
    ``block_until_ready`` on the values you hand back, records the wall
    time into the ``obs_wall_seconds{scope=...}`` histogram and an
    optrace span, so modeled FLOPs/bytes ratios can be paired with
    measured wall time at the same call sites.
"""
from __future__ import annotations

import contextlib
import time
import warnings
from typing import Any, Iterator

import jax

from repro.obs import annotate, metrics, optrace

_ACTIVE_DIR: str | None = None


def active() -> bool:
    return _ACTIVE_DIR is not None


def start(log_dir: str) -> bool:
    """Begin a ``jax.profiler`` trace into ``log_dir``.  Returns False
    (with a warning) when the profiler cannot start on this platform."""
    global _ACTIVE_DIR
    if _ACTIVE_DIR is not None:
        warnings.warn(f"profiler already active ({_ACTIVE_DIR})",
                      stacklevel=2)
        return True
    try:
        jax.profiler.start_trace(log_dir)
    except Exception as e:  # platform/profiler-support dependent
        warnings.warn(f"jax profiler unavailable: {e}", stacklevel=2)
        return False
    _ACTIVE_DIR = log_dir
    return True


def stop() -> str | None:
    """End the active trace; returns the log dir it wrote to (or None)."""
    global _ACTIVE_DIR
    if _ACTIVE_DIR is None:
        return None
    out, _ACTIVE_DIR = _ACTIVE_DIR, None
    try:
        jax.profiler.stop_trace()
    except Exception as e:
        warnings.warn(f"jax profiler stop failed: {e}", stacklevel=2)
        return None
    return out


class WallScope:
    """Mutable handle yielded by :func:`wall`; call :meth:`ready` on the
    computation's outputs so the timed interval includes device work."""

    __slots__ = ("name", "elapsed_s")

    def __init__(self, name: str):
        self.name = name
        self.elapsed_s = 0.0

    def ready(self, *values: Any) -> None:
        for v in values:
            jax.block_until_ready(v)


@contextlib.contextmanager
def wall(name: str, **args: Any) -> Iterator[WallScope]:
    """Time a host scope (caller blocks on device values via
    ``scope.ready(...)``); records ``obs_wall_seconds{scope=name}`` and an
    optrace span when telemetry is enabled."""
    scope = WallScope(name)
    t0 = time.perf_counter()
    try:
        # the host span: on the profiler's timeline beside the named device
        # scopes it encloses, and in optrace's ring while telemetry is on
        with annotate.host_scope(name, cat="wall", **args):
            yield scope
    finally:
        scope.elapsed_s = time.perf_counter() - t0
        if optrace.enabled():
            metrics.histogram(
                "obs_wall_seconds", "measured wall time by scope",
                labels=("scope",)).observe(scope.elapsed_s, scope=name)
