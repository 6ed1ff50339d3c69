"""Regenerate ``three_party_trace.json`` from the engine's K=3 path.

The trace pins the K=2-feature-party (three parties total) round loop of
``repro.core.engine`` bit-for-bit — run this ONLY when an intentional
numeric change invalidates the golden, and say so in the commit message.

    PYTHONPATH=src python tests/golden/record_three_party.py
"""
import json
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from test_engine import _run_three_party_trace  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "three_party_trace.json")


def main():
    rows = _run_three_party_trace(rounds=20)
    with open(OUT, "w") as f:
        json.dump({"celu": rows, "jax_version": jax.__version__}, f,
                  indent=1)
    print(f"wrote {OUT}: {len(rows) - 1} rounds")
    print("first:", rows[0])
    print("tail: ", rows[-1])


if __name__ == "__main__":
    main()
