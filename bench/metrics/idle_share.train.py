"""Share of the traced window in which no operation ran on the device,
while whole quantizer trainings ran back to back (device trace)."""


def read(run):
    return 100.0 * run.trace.idle_share
