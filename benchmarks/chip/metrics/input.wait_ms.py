"""input.wait_ms: host milliseconds per step spent in ``ShardedLoader.get``
(making the batch and placing it on the chips), measured around each
call of the traced window. Moves tokens_per_s: the launcher's loop makes
the next batch only after the last step's loss is read, so this time is
device idle time."""


def read(run):
    if not run.input_s:
        return None
    return 1e3 * sum(run.input_s) / len(run.input_s)
