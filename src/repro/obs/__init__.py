"""Observability primitives for the Multi-SPIN serving stack.

``repro.obs`` is dependency-free (stdlib only — no jax, no numpy) so the
gateway and telemetry layers stay importable without an accelerator stack,
and so instrumented hot paths pay nothing when tracing is off.

The one subsystem here today is the span tracer (``repro.obs.trace``):
nested wall-clock spans, optionally mirrored onto the JAX profiler's trace
(``Tracer(profiler=True)``, lazy jax import), and JAX's own trace, lower
and compile intervals, exported as Chrome trace-event JSON that loads
directly in Perfetto / chrome://tracing.
"""

from repro.obs.trace import (
    NULL_SPAN,
    Span,
    Tracer,
    active,
    install,
    span,
    tracing,
    uninstall,
)

__all__ = [
    "NULL_SPAN",
    "Span",
    "Tracer",
    "active",
    "install",
    "span",
    "tracing",
    "uninstall",
]
