"""Mean seconds per sweep of one of the program's own ALS phases
(`pio_train_phase_seconds`) over the window."""


def read(run: dict, args: dict):
    total, n = run.get("phases", {}).get(args["phase"], (0.0, 0))
    if n <= 0:
        return None
    return total / n
