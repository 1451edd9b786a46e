"""scheduler.dispatch_ms.suite: host milliseconds in Scheduler.dispatch
(planning, staging, device_put, enqueue) per chunk dispatched in the
window, from the harness's span around each call."""


def read(run):
    jobs = run.records.get("job_steps")
    n = sum(j["dispatches"] for j in jobs or [])
    if not n:
        return None
    return run.span_seconds("dispatch") / n * 1e3
