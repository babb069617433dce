"""Bridge into jax's own instrumentation: compile events.

:func:`install_compile_listener` subscribes to ``jax.monitoring``
backend-compile duration events and forwards them to whatever
Observability is ambient *at event time*.  jax listeners are global and
effectively permanent, so exactly one process-wide dispatcher is installed
(by every :func:`repro.obs.observe`), a no-op while observability is off.

There is no profiler hook: whoever starts a ``jax.profiler`` capture gets
the live tracer's spans in it as ``repro:<name>`` annotations.
"""

from __future__ import annotations

__all__ = ["install_compile_listener"]

_listener_installed = False

#: jax.monitoring event names worth surfacing (backend compile time is the
#: dominant one-off cost this repo cares about — one compile per key-set).
_EVENTS_OF_INTEREST = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)


def _dispatch(event: str, duration_secs: float, **kwargs) -> None:
    from repro.obs import current

    ob = current()
    if not ob.enabled:
        return
    if not any(event.startswith(e) for e in _EVENTS_OF_INTEREST):
        return
    short = event.rsplit("/", 1)[-1]
    ob.registry.counter(f"jax.{short}").inc()
    ob.registry.histogram(f"jax.{short}_s").record(duration_secs)
    ob.tracer.instant(f"jax:{short}", scope="p", duration_s=duration_secs)


def install_compile_listener() -> bool:
    """Install the process-wide jax.monitoring dispatcher (idempotent).

    Returns True if the listener is active (now or from an earlier call),
    False when jax.monitoring is unavailable.
    """
    global _listener_installed
    if _listener_installed:
        return True
    try:
        from jax import monitoring
    except Exception:  # pragma: no cover - jax always present in this repo
        return False
    register = getattr(monitoring, "register_event_duration_secs_listener", None)
    if register is None:  # pragma: no cover - older/newer jax
        return False
    register(_dispatch)
    _listener_installed = True
    return True

