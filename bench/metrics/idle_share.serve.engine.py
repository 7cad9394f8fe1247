"""Share of the traced part of the serving window in which the device
ran nothing while the host was inside an ``engine.*`` span other than a
``*.sync`` one (preparing inputs, dispatching, recording, prefill,
splice, first token), on the device trace's clock, in percent
(``bench/program.py``)."""
import program


def read(rec):
    if rec["kind"] != "serve":
        return None
    red = program.of(rec)
    if red is None:
        return None
    return program.idle_share(red, "engine.", but=".sync")
