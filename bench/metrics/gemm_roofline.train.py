"""The training step's matmuls against their roofline, in %: the least
time one chip's share of the step's matmul operations and bytes could take
at the chip's peaks, over the device time of the matmul ops per step in
the trace (averaged over the chips)."""


def read(rec):
    tr = rec.get("trace")
    if rec["kind"] != "train" or not tr or not tr["devices"]:
        return None
    steps = tr["steps"].get("bench.train_step", 0)
    matmul_s = tr["op_s"].get("matmul", 0.0)
    if not steps or matmul_s <= 0.0:
        return None
    peaks, chips = rec["peaks"], rec["chips"]
    least = max(rec["step_flops"] / chips / peaks["bf16_flops"],
                rec["step_bytes"] / chips / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (matmul_s / steps)
