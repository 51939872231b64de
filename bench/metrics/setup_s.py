"""Set-up time: process start until the window opens (data, build,
upload, warm-up and, on a cold cache, compilation)."""


def read(run):
    return run.setup_s
