"""Images answered in the window over the window's length (host clock).
The window runs from the first send to the last answer."""


def read(run):
    return run.completed / run.window_s
