"""How late the open-loop generator sent: 95th percentile of actual send
minus scheduled arrival.  A closed loop has no schedule: nothing to read."""


def read(run: dict, args: dict):
    return run.get("late_p95_ms")
