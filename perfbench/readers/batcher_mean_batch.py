"""Requests per dispatched batch over the window, from
`SharedBatcher.stats()`."""


def read(run: dict, args: dict):
    if not run.get("batches"):
        return None
    return run["requests"] / run["batches"]
