"""Share of the traced window in which a request was in the system (from
its due time to its last token) and no operation ran on the chip: time
the host, not the device, held a request back."""


def read(run):
    return 100.0 * run.trace.idle_share(run.in_system_ms())
