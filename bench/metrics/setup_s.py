"""Set-up seconds: process start to the first timed step (loading,
weights, compilation or the compile cache, first and warm-up steps)."""


def read(rec):
    return rec["setup_s"]
