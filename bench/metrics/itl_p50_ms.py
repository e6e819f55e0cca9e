"""Median gap between consecutive output tokens of a request, over every
gap that ends in the window."""
from harness import client


def read(run):
    p = client.percentile(client.token_gaps(run.rec), 50)
    return None if p is None else p * 1e3
