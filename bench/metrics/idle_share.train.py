"""Share of the traced part of the training window in which no operation
ran on the device, in percent."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "train" or not t or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
