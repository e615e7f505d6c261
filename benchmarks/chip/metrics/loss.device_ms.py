"""loss.device_ms: device milliseconds per step of the ops under the
program scope ``loss`` (final norm, head and chunked cross-entropy,
forward and backward), mean over chips. Moves tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    return None if s is None else s.scope_ms("loss")
