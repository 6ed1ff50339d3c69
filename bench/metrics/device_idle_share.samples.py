"""Share of the traced window in which no operation ran on the device
(averaged over the chips the step uses), in %."""


def read(rec):
    t = rec["trace"]
    return None if t is None else 100.0 * t.idle_share()
