"""Peak device memory of the process, ``memory_stats()["peak_bytes_in_use"]``
read after the window, in GiB."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2**30
