"""host_cache.transfer_ms: milliseconds per step during which a transfer
between HBM and host memory (the fcdp cache's offload and reload, memory
space S(5)) is in flight, mean over chips. Moves tokens_per_s."""


def read(run):
    if not any(run.red.host.values()):
        return None
    return run.per_step_ms(run.red.host)
