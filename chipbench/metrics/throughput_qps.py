"""Exact answers completed during the window, over the window's length."""


def read(run):
    return run.completed_in_window / run.seconds
