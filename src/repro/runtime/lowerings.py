"""A process-wide count of program lowerings (jaxpr to MLIR module), from
one ``jax.monitoring`` listener. A step that lowers nothing ran a program
already compiled in this process; a step that lowers once or more
recompiled (or, with the persistent cache, at least looked it up)."""
from __future__ import annotations

import jax

EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

_count = 0
_listening = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    global _count
    if event == EVENT:
        _count += 1


def count() -> int:
    """Lowerings so far in this process. The first call installs the
    listener; lowerings before it are not counted."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return _count
