"""Roofline share of the fused dequant-attention read over the quantized
KV cache (``kernels/kv_dequant``, ``kv_attention_rows``) in the traced
part of the window: the summed least time of its calls (``bench/work.py``)
over the summed device time of its ops, in percent."""


def read(rec):
    t, w = rec.get("trace"), rec.get("work")
    if not t or not w or not t["ops_s"].get("kv_attention_rows"):
        return None
    kernel_s = t["ops_s"]["kv_attention_rows"]
    return 100.0 * w["kv_attention"]["least_s"] / kernel_s
