"""matmul_roofline: the train step's matmul ops (XLA fusions holding a
dot or convolution) as a share of their roofline, in percent: the sum of
each op's least time (the larger of its FLOPs over the chip's peak and
its HBM bytes over the chip's bandwidth, counted from the compiled
program by ``flops.hlo_op_costs``) over the sum of their times in the
trace. Moves mfu."""


def read(run):
    return None if run.matmul is None else run.matmul["share"]
