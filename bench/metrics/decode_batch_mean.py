"""Slots decoded per decode step in the traced window, from each step's
info."""


def read(run):
    dec = [s.decoded for s in run.traced_steps() if s.decoded]
    return sum(dec) / len(dec) if dec else None
