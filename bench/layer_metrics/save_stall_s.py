"""Seconds the step loop stood still in each periodic save: the program's
``save_stall`` observations (extract, up to the host copy's last byte),
their total over their count."""


def read(rec):
    stalls = rec.observed.get("save_stall", [])
    if not stalls:
        return None
    return sum(stalls) / len(stalls)
