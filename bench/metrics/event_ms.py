"""Host wall time per scheduler event: the whole window over every event
handled in it (a superseded departure popped on the way belongs to the
event after it)."""


def read(run):
    if not run.units:
        return None
    return run.window_s * 1e3 / len(run.units)
