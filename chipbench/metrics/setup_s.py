"""Set-up: from the start of the process to the start of the window
(generation, index build, engine construction, warm-up), host clock."""


def read(run):
    return run.setup_s
