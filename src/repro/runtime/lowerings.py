"""Process-wide counts of program lowerings (jaxpr to MLIR module) and
of the attention paths that traces took, from ``jax.monitoring``
listeners. A step that lowers nothing ran a program already compiled in
this process; a step that lowers once or more recompiled (or, with the
persistent cache, at least looked it up). Each traced self-attention
call records whether it took the fused kernel or the chunked path, as
an ``ATTENTION_EVENT`` with ``path`` 'kernel' or 'chunked'."""
from __future__ import annotations

import jax

EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
ATTENTION_EVENT = "/repro/attention/path"
ATTENTION_PATHS = ("kernel", "chunked")

_count = 0
_paths = dict.fromkeys(ATTENTION_PATHS, 0)
_listening = False


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    global _count
    if event == EVENT:
        _count += 1


def _on_event(event: str, **kwargs) -> None:
    if event == ATTENTION_EVENT and kwargs.get("path") in _paths:
        _paths[kwargs["path"]] += 1


def _listen() -> None:
    """Install the listeners once; events before it are not counted."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def count() -> int:
    """Lowerings so far in this process."""
    _listen()
    return _count


def attention_paths() -> dict:
    """Traced self-attention calls so far in this process, by path."""
    _listen()
    return dict(_paths)
