"""Set-up seconds: from process start to the window's start (imports, the
compile cache, the model and its warm checks)."""


def read(run):
    return run.setup_s
