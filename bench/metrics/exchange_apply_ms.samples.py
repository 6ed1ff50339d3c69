"""Device milliseconds per traced round in the round's ``exchange_apply``
stage: the fresh optimizer steps and the workset inserts
(``bench/stages.py`` says how an operation is placed)."""
import stages


def read(rec):
    return stages.ms_per_round(rec, stages.EXCHANGE_APPLY)
