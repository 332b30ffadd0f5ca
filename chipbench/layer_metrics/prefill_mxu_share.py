"""Kernels, whole-prompt prefill: the operations a prefill needs (the
family's `prefill_flops(config, bucket, pairs)`: the matrices over the
bucket's rows, the routed pairs the step really sent to held experts,
attention inside the causal band) over the chip's bf16 peak, over the device
time of that `jit_serving_prefill*` program in the traced slice; median over
the traced prefills, %. Compute-bound. `bucket` and `moe_pairs` are on the
prefill's own `serving.prefill` span; the program computes whole tiles and
whole blocks, never less than is counted, so the share cannot pass 100."""
from chipbench.harness import context
from chipbench.trace import reduce as tr


def read(ctx):
    flops = getattr(ctx.family, "prefill_flops", None)
    if flops is None or ctx.trace is None:
        return None
    shares = []
    for s in ctx.named("serving.prefill"):
        if "bucket" not in s.get("attrs", {}):
            continue
        b = tr.to_trace_s(ctx.trace, s["ts"] / 1e6)
        device_s = sum(d for t, d, name in ctx.trace["modules"]
                       if name.startswith("jit_serving_prefill")
                       and b <= t + d / 2 <= b + s["dur"] / 1e6)
        if device_s > 0:
            shares.append(100.0 * flops(ctx.cell.config, s["attrs"]["bucket"],
                                        s["attrs"].get("moe_pairs"))
                          / ctx.peaks["bf16_flops_per_s"] / device_s)
    return context.median(shares)
