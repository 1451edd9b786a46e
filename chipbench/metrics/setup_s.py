"""setup_s: seconds from process start to the opening of the window:
imports, building the system, weights, warm-up and every compile."""


def read(run):
    return run.setup_s
