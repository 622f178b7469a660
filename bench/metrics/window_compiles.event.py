"""Device programs built inside the window, compiled or loaded from the
persistent compilation cache. Expected 0."""


def read(run):
    return run.compiles
