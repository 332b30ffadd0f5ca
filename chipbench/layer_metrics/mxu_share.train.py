"""Kernels, training: the model's FLOPs per step and chip (the family's
`train_flops_per_step`, from shapes, forward and backward) over the chip's
bf16 peak, over the median device time of the step program in the traced
slice, %. Compute-bound lower bound over measured time, so it cannot pass
100."""
from chipbench.harness import context


def read(ctx):
    flops = getattr(ctx.family, "train_flops_per_step", None)
    prog = ctx.step_program()
    if flops is None or prog is None:
        return None
    per_chip = flops(ctx.cell.config, ctx.record["global_batch"]) / ctx.cell.chips
    least_s = per_chip / ctx.peaks["bf16_flops_per_s"]
    return 100.0 * least_s / context.median(prog[1])
