"""Message-hops of every placement scored in the window, over the window,
in millions per second. A message-hop is one (message, server) visit on
the message's route, counted from the placement and the cluster by
``harness.reference.message_hops``, whatever the program does to score
the placement."""


def read(run):
    if not run.calls or run.window_s <= 0:
        return None
    return run.hops / run.window_s / 1e6
