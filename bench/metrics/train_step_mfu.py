"""Model FLOP/s of the training window over the chips' bf16 peak, in %:
the step's operations from the configuration's widths (no recompute,
no padding), times the steps the window ran, over its seconds."""


def read(rec):
    if rec["kind"] != "train" or not rec["steps"]:
        return None
    achieved = rec["steps"] * rec["step_flops"] / rec["window_s"]
    return 100.0 * achieved / (rec["chips"] * rec["peaks"]["bf16_flops"])
