"""Peak device memory in use over the process, read after the window
(``memory_stats()["peak_bytes_in_use"]``)."""


def read(run):
    return run.peak_bytes / 2 ** 30 if run.peak_bytes > 0 else None
