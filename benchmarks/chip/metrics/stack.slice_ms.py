"""stack.slice_ms: device milliseconds per step of the ops under the
program scope ``stack`` and under no inner scope: the layer scan's own
slicing and stashing of its stacked buffers (weights, layer inputs,
the host cache's staging), mean over chips. Moves tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    return None if s is None else s.scope_ms("stack")
