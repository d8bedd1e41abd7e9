"""Recompile sentinel: count XLA backend compilations via ``jax.monitoring``.

The static passes keep the *code* honest; this keeps the *runtime* honest.
PR 5's layout pinning exists so the chunk program compiles exactly once
per (strategy, mesh, knobs) job — a silent recompile (layout flip, shape
drift in the candidate remap, a host int leaking into the carry) costs
more than the chunk it dispatches.  ``jax.monitoring`` emits exactly one
``/jax/core/compile/backend_compile_duration`` event per program that
reaches the backend — compiled by XLA or loaded from the persistent
compilation cache (the event times the whole ``compile_or_get_cached``
call) — and none on an in-memory cache hit.  So "no silent recompiles" is
an assertable number, and it reads the same on a cold and a warm
persistent cache::

    with CompileCounter() as cc:
        run_federated(..., driver="scan")
    assert cc.compiles == expected

Each counter also sums the events' durations (``seconds``): the scan
driver reports that sum as ``compile_s``, so a compile inside a chunk
dispatch is booked as set-up and not as host work.

``jax.monitoring`` has no public unregister, so a single module-level
dispatcher is registered once (lazily, on first use) and forwards to
whichever counters are active; exiting a ``CompileCounter`` just removes
it from the active set.  Counters therefore nest, and each one only sees
compiles that happen inside its ``with`` block.
"""
from __future__ import annotations

import threading
from typing import List

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_active: List["CompileCounter"] = []
_registered = False


def _dispatch(event: str, duration: float, **kwargs) -> None:
    if event != _COMPILE_EVENT:
        return
    with _lock:
        for counter in _active:
            counter._count += 1
            counter._seconds += duration


def _ensure_registered() -> None:
    global _registered
    with _lock:
        if _registered:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_dispatch)
        _registered = True


class CompileCounter:
    """Context manager counting XLA backend compiles (or persistent-cache
    loads) inside its block, and the seconds they took."""

    def __init__(self) -> None:
        self._count = 0
        self._seconds = 0.0

    @property
    def compiles(self) -> int:
        return self._count

    @property
    def seconds(self) -> float:
        return self._seconds

    def __enter__(self) -> "CompileCounter":
        _ensure_registered()
        with _lock:
            self._count = 0
            self._seconds = 0.0
            _active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        with _lock:
            try:
                _active.remove(self)
            except ValueError:
                pass

    def delta(self) -> "_Delta":
        """Sub-interval helper: ``with cc.delta() as d: ...; d.compiles``."""
        return _Delta(self)


class _Delta:
    """Compiles attributed to one sub-interval of an active counter —
    used by the scan driver to attribute compiles to individual chunk
    dispatches without a second listener."""

    def __init__(self, parent: CompileCounter) -> None:
        self._parent = parent
        self._start = 0
        self.compiles = 0

    def __enter__(self) -> "_Delta":
        self._start = self._parent.compiles
        return self

    def __exit__(self, *exc) -> None:
        self.compiles = self._parent.compiles - self._start


def assert_compiles(counter: CompileCounter, expected: int, what: str) -> None:
    """Raise with a diagnostic if the count drifted from ``expected``."""
    if counter.compiles != expected:
        raise AssertionError(
            f"{what}: expected exactly {expected} XLA compilation(s), "
            f"observed {counter.compiles} — a layout/shape drifted between "
            "dispatches (the silent-recompile failure mode PR 5 pinned "
            "layouts to prevent)"
        )
