"""step.compiled_hbm_gb: the compiled train step's device memory per chip
from ``memory_analysis()`` (arguments + outputs - aliased + temporaries),
in GB. Beside hbm_peak_gb it says whether the step or the set-up sets
the peak. Moves hbm_peak_gb."""


def read(run):
    return None if run.compiled_bytes is None else run.compiled_bytes / 1e9
