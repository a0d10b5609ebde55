"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals over the window."""


def read(run: dict, args: dict):
    trace = run.get("trace")
    if trace is None:
        return None
    return 100.0 * trace.idle_share
