"""Host time of one Engine.step() in the traced window: the summed span
time of the harness's span around each step over the steps."""


def read(run):
    steps = run.traced_steps()
    if not steps:
        return None
    return 1e3 * sum(s.end - s.start for s in steps) / len(steps)
