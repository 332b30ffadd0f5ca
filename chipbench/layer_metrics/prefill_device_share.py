"""Scheduler and admission: device seconds of the prefill programs (compiled
programs named `jit_serving_prefill*`) over the device seconds of all
programs run in the traced slice, %: how much of the device the prompts of
newly admitted requests take from the sequences that are decoding."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["modules"]:
        return None
    # the profiler names a program "jit_<function>(<fingerprint>)"
    prefill = sum(d for _, d, name in ctx.trace["modules"]
                  if name.startswith("jit_serving_prefill"))
    return 100.0 * prefill / sum(d for _, d, _ in ctx.trace["modules"])
