"""Model FLOP/s utilization of prefill: model FLOPs of the traced
window's ``engine.prefill_chunk`` spans (2 per matmul weight per token
plus attention over each token's context, ``bench/work.py``) over the
device time of its ``engine_prefill`` programs times the chip's bf16
peak, in percent (``bench/program.py``)."""
import program
import work


def read(rec):
    if rec["kind"] != "serve":
        return None
    red = program.of(rec)
    if red is None or not red["prefill_chunks"]:
        return None
    device_s = red["programs"].get("engine_prefill", {}).get("s")
    if not device_s:
        return None
    m = program.model_of(red["workload"])
    flops = sum(2.0 * work.matmul_params(m) * n
                + m["L"] * sum(work.mixer_flops(m, c)
                               for c in range(start + 1, start + n + 1))
                for start, n in red["prefill_chunks"])
    return 100.0 * flops / (device_s * rec["peaks"]["bf16_flops_per_s"])
