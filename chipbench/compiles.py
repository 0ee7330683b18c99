"""Count XLA backend compiles: JAX fires the monitoring event
``/jax/core/compile/backend_compile_duration`` once per executable it
builds, and a persistent-cache hit fires nothing.  The window runs
inside a counter; a compile there is reported, since its time would be
counted as work."""
from __future__ import annotations

import jax

_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """``with CompileCounter() as c: ...`` then ``c.compiles``."""

    _active: list = []
    _installed = False

    def __init__(self) -> None:
        self.compiles = 0
        self.seconds = 0.0

    @classmethod
    def _listen(cls, event: str, duration_secs: float, **_kw) -> None:
        if event == _EVENT:
            for c in cls._active:
                c.compiles += 1
                c.seconds += duration_secs

    def __enter__(self) -> "CompileCounter":
        if not CompileCounter._installed:
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._listen)
            CompileCounter._installed = True
        CompileCounter._active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        CompileCounter._active.remove(self)
