"""Peak device memory of the run in GB (1e9 bytes) on the fullest chip,
the executables' temporaries included: the allocator's peak of live
buffers (``peak_bytes_in_use``) plus its peak reservation for program
temporaries (``peak_bytes_reserved``), read after the window."""


def read(obs):
    b = obs.get("memory_peak_bytes")
    return None if b is None else b / 1e9
