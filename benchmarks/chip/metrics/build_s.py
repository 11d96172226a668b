"""Host span around ``IVMEngine.build``, ending in ``block_until_ready``
of the engine state."""


def read(run):
    return run.setup["build_s"]
