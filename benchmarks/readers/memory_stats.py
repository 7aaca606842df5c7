"""Reads ``memory_stats()["peak_bytes_in_use"]`` of the fullest device."""


def read(run, params):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / float(params.get("divide_by", 1e9))
