"""fps (frames/s, host clock): frames whose results the client held by
the end of the window, over the window's length."""


def read(run):
    return len(run.window.in_window()) / run.window.seconds
