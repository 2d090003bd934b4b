"""Share of the traced window in which no operation ran on the device:
1 minus the union of device-op intervals over the window, mean over the
chips used."""


def read(run, name):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
