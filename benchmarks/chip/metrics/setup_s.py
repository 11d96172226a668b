"""Process start to the window's open: device init, data, build,
compiles and warm-up."""


def read(run):
    return run.setup["setup_s"]
