"""Peak device memory in use after the window, set-up included: the
device allocator's ``peak_bytes_in_use`` on the fullest chip, in GB."""


def read(run):
    """Peak bytes over 1e9; nothing when the backend reports none."""
    return run.peak_bytes / 1e9 if run.peak_bytes else None
