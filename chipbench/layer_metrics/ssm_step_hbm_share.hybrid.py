"""Kernels, the recurrence step over the state plane
(`mxnet_tpu/ops/pallas_ssm_step.py`, `ssm_step` in the device trace) in a
model only SOME of whose layers keep a state: the bytes its calls must move
(the family's `ssm_step_bytes(config, rows)` a call: each row's state read
once and written once, and the row's small operands; one call a STATE layer,
counted from the pattern's letters and not from `num_hidden_layers`, of every
decode program run in the traced slice, `rows` the median `state_rows` of the
traced steps) over the chip's HBM bandwidth, over the device seconds of the
operations named `ssm_step` in the slice, %. Bandwidth-bound. A padded row's
visit to the null slot is time and is not counted as bytes, so the share is a
lower bound. `ssm_step_hbm_share` reads the same where every layer keeps a
state."""
from chipbench.harness import context


def read(ctx):
    step_bytes = getattr(ctx.family, "ssm_step_bytes", None)
    cfg = ctx.cell.config
    if step_bytes is None or ctx.trace is None \
            or "hybrid_override_pattern" not in cfg:
        return None
    seconds = sum(s for name, s in ctx.trace["ops"].items()
                  if name == "ssm_step" or name.startswith("ssm_step."))
    rows = context.median([s["attrs"]["state_rows"] for s, _ in
                           ctx.steps_in_trace("serving.decode", True)
                           if "state_rows" in s["attrs"]])
    programs = sum(1 for _, _, name in ctx.trace["modules"]
                   if name.startswith("jit_serving_decode"))
    if not seconds or not rows or not programs:
        return None
    return 100.0 * programs * cfg["hybrid_override_pattern"].count("M") \
        * step_bytes(cfg, rows) / ctx.peaks["hbm_bytes_per_s"] / seconds
