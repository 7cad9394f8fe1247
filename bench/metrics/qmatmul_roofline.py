"""Roofline share of the packed dequant-matmul kernel (``kernels/qmatmul``)
in the traced part of the window: the summed least time of its calls
(each the larger of FLOPs over the bf16 peak and stored bytes over HBM
bandwidth, ``bench/work.py``) over the summed device time of its
``qmatmul`` ops, in percent."""


def read(rec):
    t, w = rec.get("trace"), rec.get("work")
    if not t or not w or not t["ops_s"].get("qmatmul"):
        return None
    return 100.0 * w["qmatmul"]["least_s"] / t["ops_s"]["qmatmul"]
