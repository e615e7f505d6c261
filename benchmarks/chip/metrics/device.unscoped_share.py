"""device.unscoped_share: percent of the device's busy time spent in
ops of the core that are neither host-memory (S(5)) ops nor under any
program scope, mean over chips. None where no op ran under a scope (a
program without scopes). Moves tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    if s is None or not s.red.scoped:
        return None
    busy = s.base.red.busy
    shares = [s.red.unscoped[c] / busy[c] for c in s.red.unscoped
              if busy.get(c)]
    return 100.0 * sum(shares) / len(shares) if shares else None
