"""Share of the trainer's run in which the device stood idle outside every
step and save: idle time of the first chip under ``spoton.run`` or
``spoton.flush`` alone (the run's start, the tail the last write adds),
over the ``spoton.run`` span's length. With the two other ``idle_*``
metrics it sums to the idle share of the run."""

from harness import spans


def read(rec):
    split = spans.idle_split(rec.trace)
    return None if split is None else split["outside"]
