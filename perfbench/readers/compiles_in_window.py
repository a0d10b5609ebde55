"""Executables the program built or fetched between the window's start and
end (obs/xray's compile events); 0 is what a warmed-up run reads."""


def read(run: dict, args: dict):
    return run.get("compiles_in_window")
