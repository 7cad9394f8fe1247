"""Median host time of the program's ``train.dispatch`` span (the jitted
train step's call, until it returns) over the traced steps, in ms
(``bench/program.py``)."""
import program


def read(rec):
    if rec["kind"] != "train":
        return None
    red = program.of(rec)
    if red is None:
        return None
    return program.median_ms(red["dispatch_s"])
