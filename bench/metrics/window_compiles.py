"""Executables compiled or loaded from the compile cache while the window
ran (``bench/compiles.py``); every shape is warmed up before it, so 0."""


def read(run):
    return run.window_built
