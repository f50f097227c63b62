"""Peak device memory on the fullest chip, in GiB, read after the window:
the allocator's ``peak_bytes_in_use`` (``device.memory_stats()``: live
buffers) plus the largest temporary allocation of any program that ran
(XLA's memory analysis, on the program's ``compile`` event) — this
runtime's allocator statistics leave a running program's temporaries out."""


def read(run):
    return run.memory_peak_bytes / 2**30 if run.memory_peak_bytes else None
