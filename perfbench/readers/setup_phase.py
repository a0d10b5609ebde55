"""Seconds of one phase of set-up, by the harness's own clock."""


def read(run: dict, args: dict):
    return run["setup"].get(args["phase"])
