"""Share of the traced window in which no operation ran on the device, in
%, averaged over the chips used."""


def read(run):
    return 100.0 * run.trace.idle_share()
