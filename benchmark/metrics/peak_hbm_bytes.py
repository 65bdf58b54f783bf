"""The device's ``peak_bytes_in_use`` after the window (the fullest chip).
None where the backend reports no memory statistics."""


def read(run):
    return run.memory_peak_bytes
