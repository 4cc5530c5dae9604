"""Count what JAX compiles, so that "nothing compiled inside the window"
is a measurement and not a hope.

Every program JAX lowers, whether the compiler then builds it or the
persistent cache hands it back, reports one
``/jax/core/compile/jaxpr_to_mlir_module_duration`` event. A lowering inside
the window is a jit cache miss there, and ``correct`` is false with it.
"""

from __future__ import annotations

LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    def __init__(self):
        from jax import monitoring

        self.lowered = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._at_open = None
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, name, _secs, **_kw) -> None:
        if name == LOWERED:
            self.lowered += 1

    def _on_event(self, name, **_kw) -> None:
        if name.endswith("/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/cache_misses"):
            self.cache_misses += 1

    def open_window(self) -> None:
        self._at_open = self.lowered

    def close_window(self) -> int:
        """Programs lowered since ``open_window``."""
        return self.lowered - self._at_open
