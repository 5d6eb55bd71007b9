"""Idle share of the device over the traced window, in percent: 100 times
one minus the union of device-op intervals over the window's length."""


def read(run):
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
