"""stepper.device_us_per_step: device microseconds of the simulator's
stepper programs per simulated step, from the trace of a traced run's
slice (one job): the summed time of the cohort, batch and single stepper
programs (per chip, averaged over the chips), over the steps of that job.
A dispatch runs as many steps as its longest member, and the steps are
the simulator's own counts, which the check holds to the reference."""

MODULES = ("jit__run_cohort", "jit__run_batch", "jit__run_single",
           "jit_local")            # jit_local: the sharded cohort stepper


def read(run):
    if run.trace is None:
        return None
    steps = run.records.get("traced", {}).get("steps")
    sec, n = run.trace.seconds(
        "modules", lambda name: name.split("(")[0] in MODULES)
    if not steps or not n:
        return None
    return sec / steps * 1e6
