"""Share of the traced window in which no operation ran on the device,
in %: 1 - union of device-op intervals / window (profiler trace)."""


def read(run):
    """Idle share of the window; nothing without a chip trace."""
    if run.trace is None or not run.trace.chips:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
