"""remat.device_ms: device milliseconds per step of the ops whose
``op_name`` holds ``rematted_computation``: the forward recomputed in the
backward. It overlaps the scope metrics. Mean over chips. Moves
tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    if s is None or not any(s.red.remat.values()):
        return None
    return s.per_step_ms(s.red.remat)
