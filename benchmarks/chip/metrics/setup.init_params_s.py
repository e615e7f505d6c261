"""setup.init_params_s: seconds of the set-up phase that draws the
initial parameters (the program's counter ``setup_s["init_params"]``,
the span ``setup.init_params``). Moves setup_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    return None if s is None else (
        (s.counters or {}).get("setup_s", {}).get("init_params"))
