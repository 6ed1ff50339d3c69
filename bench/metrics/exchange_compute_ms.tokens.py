"""Device milliseconds per traced round in the round's ``exchange_compute``
stage: the parties' forward passes, the wire, party B's loss and every
fresh gradient (``bench/stages.py`` says how an operation is placed)."""
import stages


def read(rec):
    return stages.ms_per_round(rec, stages.EXCHANGE_COMPUTE)
