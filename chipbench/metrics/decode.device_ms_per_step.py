"""decode.device_ms_per_step: device milliseconds of the engine's jitted
decode step (the ``jit_decode_step`` program) per call, from the trace."""

MODULE = "jit_decode_step"


def read(run):
    if run.trace is None:
        return None
    sec, n = run.trace.seconds(
        "modules", lambda name: name.split("(")[0] == MODULE)
    return sec / n * 1e3 if n else None
