"""Share of the traced part of the serving window in which no operation
ran on the device, in percent."""


def read(rec):
    t = rec.get("trace")
    if rec["kind"] != "serve" or not t or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
