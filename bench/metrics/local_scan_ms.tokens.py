"""Device milliseconds per traced round in the round's ``local_scan`` stage:
the R local updates, each a workset draw, a weighted gradient and an
optimizer step (``bench/stages.py`` says how an operation is placed)."""
import stages


def read(rec):
    return stages.ms_per_round(rec, stages.LOCAL_SCAN)
