"""init.hbm_peak_gb: the most device memory in use, over the mesh's
chips, from the start of the process to the end of set-up, before any
step (the program's counter ``init_peak_bytes``), in GB. Beside
hbm_peak_gb and step.compiled_hbm_gb it says whether set-up or the step
sets the peak. Moves hbm_peak_gb."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    peak = None if s is None else (s.counters or {}).get("init_peak_bytes")
    return None if peak is None else peak / 1e9
