"""gather.stage1_ms: milliseconds per step in which a collective under
the program scope ``fcdp.gather1`` is in flight or run by the core: the
stage-1 (pod) all-gather in the forward, and in the backward its
transpose, the gradient's reduce-scatter (and under zero3 the gather
again). Max over chips. Moves tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    if s is None or not any(s.red.gather1.values()):
        return None
    return s.per_step_ms(s.red.gather1, max)
