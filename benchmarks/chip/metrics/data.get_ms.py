"""data.get_ms: host milliseconds per step in the program span
``data.get`` (``ShardedLoader.get``: the batch made and placed), mean
over the window's calls; the inside twin of input.wait_ms. Moves
tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    return None if s is None else s.span_ms("data.get")
