"""host_cache.exposed_ms: milliseconds per step in which a host-memory
transfer is in flight, or the core waits for one, while no compute op
runs on that chip; mean over chips. Moves tokens_per_s."""


def read(run):
    if not any(run.red.host.values()):
        return None
    return run.per_step_ms(run.red.host_exposed)
