"""Regenerate ``two_party_trace.json`` from the engine's K=1 path.

The trace was originally recorded from the pre-engine seed implementation;
the unified engine reproduces it bit-for-bit, so this recorder (which runs
the engine directly) emits the byte-identical file.  CI's golden-drift
check runs it and ``git diff --exit-code tests/golden/`` — a silent
numeric change to the K=1 round loop shows up as a dirty tree.  Re-record
ONLY when an intentional numeric change invalidates the golden, and say so
in the commit message.

    PYTHONPATH=src python tests/golden/record_two_party.py
"""
import json
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from test_engine import _run_trace  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "two_party_trace.json")


def main():
    trace = {proto: _run_trace(proto, via_shim=False, rounds=20)
             for proto in ("vanilla", "fedbcd", "celu")}
    trace["jax_version"] = jax.__version__
    with open(OUT, "w") as f:
        json.dump(trace, f, indent=1)
    print(f"wrote {OUT}: {len(trace) - 1} protocols x "
          f"{len(trace['celu']) - 1} rounds on jax {jax.__version__}")
    print("celu tail:", trace["celu"][-1])


if __name__ == "__main__":
    main()
