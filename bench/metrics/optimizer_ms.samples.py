"""Device milliseconds per traced round under the ``optimizer`` scope, in
``exchange_apply`` and in ``local_scan`` together: the 1 + n_local
AdaGrad steps of a round (``bench/stages.py`` says how an operation is
placed)."""
import stages


def read(rec):
    return stages.ms_per_round(rec, stages.OPTIMIZER)
