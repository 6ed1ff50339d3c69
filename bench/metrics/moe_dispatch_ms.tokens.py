"""The part of ``moe_ms.tokens`` outside the grouped expert matmuls
(``moe_experts``): routing, the sort, the gathers, the scatter-add and
the combine, in device milliseconds per traced round."""
import moe_scopes


def read(rec):
    s = moe_scopes.seconds(rec)
    return None if s is None else 1e3 * (s[0] - s[1])
