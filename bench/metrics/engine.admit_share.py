"""Share of the serving window spent inside ``Engine.submit`` (prefill of
an admitted prompt and its first token), from the benchmark's host
spans around the call, in percent."""


def read(rec):
    if rec["kind"] != "serve" or not rec["submit_s"]:
        return None
    return 100.0 * sum(rec["submit_s"]) / rec["window_s"]
