"""Median wall time of one ``Engine.step`` (one decode tick of every
active slot), from the benchmark's host spans around the call, in ms."""
import statistics


def read(rec):
    if rec["kind"] != "serve" or not rec["step_s"]:
        return None
    return 1e3 * statistics.median(rec["step_s"])
