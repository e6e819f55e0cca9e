"""Set-up: from the start of the process to the window's opening, warm-in
included (import, device, weights, pools, warm-up, warm-in traffic)."""


def read(run):
    return run.setup_s
