"""Peak device memory of the run (`memory_stats()["peak_bytes_in_use"]`
after the window, before the reference runs), in GiB."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
