"""The readings a cell's limits are set from, in one process on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 101-112 \
        --control-seeds 101-103 [--rehearse]

For every seed: the program's compared numbers (``check.gaps`` of its
first steps against the float32 reference), as a run of ``bench/run.py``
computes them.  For the control seeds also: the control, the reference
computed one precision step below what the configuration states, put in
the program's place; and the half-batch fault, the reference over the
first half of every batch put in the program's place; and each fault
named in ``--faults``, planted in the program (``frozen``, ``half_batch``,
``no_exchange``: see ``bench/tests/test_faults.py``).  One JSON line per
seed; nothing here is run by the benchmark's own runs.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def seeds(spec: str):
    out = []
    for part in spec.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="",
                    help="comma-separated faults to plant in the program")
    ap.add_argument("--no-half-batch", action="store_true",
                    help="read the control alone on the control seeds")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, rehearse=args.rehearse)
    jax = harness.configure_jax(args.rehearse, cell.chips)
    dev = harness.device_info(jax)
    harness.say(f"[calibrate] {dev} {cell.name}")
    if not args.rehearse and dev["platform"] != "tpu":
        raise SystemExit(f"calibrate: no TPU: JAX found {jax.devices()}")
    sys.path.insert(0, os.path.join(cell.root, "src"))
    control = set(seeds(args.control_seeds))
    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        run = cell.driver.Run(cell, seed)
        run.release()
        ref = run.reference()
        out = {"cell": cell.name, "seed": seed,
               "program": run.numbers(ref=ref)}
        if seed in control:
            out["control"] = run.numbers(ref=ref,
                                         prog=run.reference(control=True))
            if not args.no_half_batch:
                out["half_batch"] = run.numbers(
                    ref=ref, prog=run.reference(rows=cell.job["batch"] // 2))
            for fault in filter(None, args.faults.split(",")):
                bad = cell.driver.Run(cell, seed, fault=fault)
                bad.release()
                out["fault_" + fault] = bad.numbers(ref=ref)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
