"""Device milliseconds per traced round under the MoE layers' ``moe``
scope, in every stage of the round: routing, dispatch, the grouped expert
matmuls and the combine, forward and backward (``bench/moe_scopes.py``
says how an operation is placed)."""
import moe_scopes


def read(rec):
    s = moe_scopes.seconds(rec)
    return None if s is None else 1e3 * s[0]
