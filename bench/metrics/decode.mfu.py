"""Model FLOP/s utilization of decoding: model FLOPs of every decode tick
in the window (2 per matmul weight per active row plus attention over
its context) over the ticks' summed wall time times the chip's bf16
peak, in percent."""


def read(rec):
    if rec["kind"] != "serve" or not rec["step_s"]:
        return None
    busy = sum(rec["step_s"]) * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * rec["decode_flops"] / busy
