"""Share of the traced device busy time that ``bench/stages.py`` gives to no
stage of the round (operations no rule places, and loop control), in %."""
import stages


def read(rec):
    return stages.unattributed_share(rec)
