"""gather.collective_exposed_ms: milliseconds per step in which a
collective (all-gather, reduce-scatter, all-reduce, all-to-all or
collective-permute) is in flight, or the core runs or waits for one,
while no compute op runs on that chip; max over chips. Moves
tokens_per_s."""


def read(run):
    if not any(run.red.collective.values()):
        return None
    return run.per_step_ms(run.red.collective_exposed, max)
