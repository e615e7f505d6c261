"""attention.kernel_share: the share of the train step's self-attention
calls that run on the fused flash-attention kernel, kernel / (kernel +
chunked), from the program's counter ``attention_paths`` (set when the
step is traced). 1.0 where every call takes the kernel; None against a
program without the counter. Moves tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    paths = None if s is None else (s.counters or {}).get("attention_paths")
    if not paths or not sum(paths.values()):
        return None
    return paths.get("kernel", 0) / sum(paths.values())
