"""device.idle_share: percent of the traced window in which no op runs on
the chip (1 - union of XLA op intervals / window), mean over chips. The
window runs from the first ``bench.input`` span to the last
``bench.loss_read`` span. Moves tokens_per_s."""


def read(run):
    if run.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
