"""Output tokens delivered in the window over the window's length: every
token of every request whose time falls inside it.  The window holds
whole steps (see ``harness.client``), so no step's time is counted
without its tokens."""
from harness import client


def read(run):
    return client.window_tokens(run.rec) / (run.rec.t1 - run.rec.t0)
