"""Model FLOP/s utilization of training: model FLOPs per step (6 per
matmul weight per token plus causal attention, no recompute) times steps
per second, over the chip's bf16 peak, in percent."""


def read(rec):
    if rec["kind"] != "train" or not rec["steps"]:
        return None
    rate = rec["step_flops"] * rec["steps"] / rec["window_s"]
    return 100.0 * rate / rec["peaks"]["bf16_flops_per_s"]
