"""Host time of one engine tick that the device waits through: the
median, over the traced ticks, of the program's ``engine.step`` span
less its ``engine.step.sync`` child (the wait for the sampled tokens),
in ms (``bench/program.py``)."""
import program


def read(rec):
    if rec["kind"] != "serve":
        return None
    red = program.of(rec)
    if red is None:
        return None
    return program.median_ms(red["tick_host_s"])
