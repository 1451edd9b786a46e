"""tokens_per_s: new tokens of every wave completed in the window over the
window's length. The window holds whole waves."""


def read(run):
    if "new_tokens" not in run.records:
        return None
    t0, t1 = run.window
    return run.records["new_tokens"] / (t1 - t0)
