"""step.lowerings: program lowerings triggered by the window's train
steps (the program's counter ``step_lowerings``, summed over them): 0
where no step of the window recompiled. Moves tokens_per_s."""
from benchmarks.chip import scoped


def read(run):
    s = scoped.of(run)
    return None if s is None else s.window_lowerings()
