"""Share of the traced part of the training window in which the device
ran nothing while the host was inside a ``train.*`` span (the batch,
the step's dispatch), on the device trace's clock, in percent
(``bench/program.py``)."""
import program


def read(rec):
    if rec["kind"] != "train":
        return None
    red = program.of(rec)
    if red is None:
        return None
    return program.idle_share(red, "train.")
