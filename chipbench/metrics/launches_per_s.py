"""launches_per_s: simulated launches completed in the window over the
window's length. The window holds whole jobs: it closes when the last job
that started in it is back."""


def read(run):
    if "launches" not in run.records or "jobs" not in run.records:
        return None
    t0, t1 = run.window
    return run.records["launches"] / (t1 - t0)
