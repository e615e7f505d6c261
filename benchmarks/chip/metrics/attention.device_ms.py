"""attention.device_ms: device milliseconds per step of the ops under the
program scope ``attention`` (forward, backward and recompute), mean over
chips. Moves tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    return None if s is None else s.scope_ms("attention")
