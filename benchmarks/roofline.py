"""§Roofline reporting + cross-pod collective accounting.

1. Aggregates results/dryrun_baseline.jsonl (written by launch.dryrun) into
   the per-(arch x shape x mesh) roofline table used by EXPERIMENTS.md.
2. Measures the pod-protocol claim: inter-pod ppermute bytes per MODEL
   UPDATE drop ~(R+1)x with CELU local updates (compiling the 2-pod round
   for two chips of a described TPU v5e with R=0 vs R=5 and parsing the
   HLO, in the calling process).
"""
from __future__ import annotations

import json
import os

from .common import csv_row

_RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results")
RESULTS = [os.path.join(_RESULTS_DIR, "dryrun_baseline.jsonl"),
           os.path.join(_RESULTS_DIR, "dryrun_final.jsonl")]
PERF = os.path.join(_RESULTS_DIR, "dryrun_perf2.jsonl")


def report_table(paths=None, tag: str = ""):
    paths = [p for p in (paths or RESULTS) if os.path.exists(p)]
    if not paths:
        csv_row("# roofline: no dryrun results",
                "(run launch.dryrun --all [--multi-pod] first)")
        return []
    seen = {}
    for path in paths:                      # later files take precedence
        with open(path) as f:
            for line in f:
                r = json.loads(line)
                if r.get("tag", "") != tag:
                    continue
                seen[(r["arch"], r["shape"], r["mesh"])] = r   # last wins
    rows = sorted(seen.values(), key=lambda r: (r["arch"], r["shape"],
                                                r["mesh"]))
    csv_row("# roofline terms (seconds/step, per-device HLO)")
    csv_row("arch", "shape", "mesh", "ok", "compute_s", "memory_s",
            "collective_s", "dominant", "useful_flops_frac", "temp_GB")
    for r in rows:
        if not r.get("ok"):
            csv_row(r["arch"], r["shape"], r["mesh"], "FAIL",
                    "-", "-", "-", "-", "-", "-")
            continue
        t = r["roofline"]
        csv_row(r["arch"], r["shape"], r["mesh"], "ok",
                f"{t['compute_s']:.4f}", f"{t['memory_s']:.4f}",
                f"{t['collective_s']:.4f}", r["dominant"],
                f"{r['useful_flops_frac']:.3f}",
                f"{r['memory']['temp_bytes'] / 1e9:.1f}")
    return rows


def pod_collective_accounting():
    """Inter-pod ppermute bytes per model update: the 2-pod round compiled
    for two chips of a described TPU v5e, in this process (nothing runs,
    so no device is taken and no child process is started).  Raises when
    the compile fails."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.pod_protocol import init_pod_state, make_pod_round
    from repro.launch.dryrun import collective_bytes
    from repro.optim import adagrad

    csv_row("# pod-protocol cross-pod bytes (2-chip v5e compile)")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:2]), ("pod",))
    put = NamedSharding(mesh, P("pod"))
    opt = adagrad(0.05)
    state = jax.eval_shape(lambda: init_pod_state(
        jax.random.PRNGKey(0), mesh, opt, n_fields=16, vocab=512,
        batch=4096, W=5, z_dim=256, hidden=256))
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=put),
        (*state, jax.ShapeDtypeStruct((2, 4096, 16), jnp.int32),
         jax.ShapeDtypeStruct((2, 4096), jnp.float32)))
    for R in (0, 3, 5, 8):
        rnd = make_pod_round(mesh, opt, R=max(R, 1), cos_xi=0.5)
        cp = collective_bytes(rnd.lower(*args).compile().as_text())[
            "collective-permute"]
        # ppermute bytes per ROUND are constant (Z_A + dZ_A, the paper's
        # 2x4MB for B=4096 z=256 fp32); CELU funds 1+R updates with them
        updates = 1 + R
        csv_row(f"R={R}" + (" (vanilla)" if R == 0 else ""),
                f"ppermute_bytes/round={cp}", f"updates/round={updates}",
                f"bytes/update={cp / updates:.0f}")


def report_perf_variants():
    """§Perf iteration results (tagged runs from dryrun_perf.jsonl)."""
    if not os.path.exists(PERF):
        return
    csv_row("# perf-iteration variants (see EXPERIMENTS.md §Perf)")
    csv_row("arch", "shape", "tag", "ok", "compute_s", "memory_s",
            "collective_s", "temp_GB")
    with open(PERF) as f:
        for line in f:
            r = json.loads(line)
            if not r.get("ok"):
                csv_row(r["arch"], r["shape"], r.get("tag", ""), "FAIL",
                        "-", "-", "-", "-")
                continue
            t = r["roofline"]
            csv_row(r["arch"], r["shape"], r.get("tag", ""), "ok",
                    f"{t['compute_s']:.4f}", f"{t['memory_s']:.4f}",
                    f"{t['collective_s']:.4f}",
                    f"{r['memory']['temp_bytes'] / 1e9:.1f}")


def cache_accounting():
    """Workset-cache roofline at the paper's deployment geometry (W=5,
    B=4096, z=256): at-rest bytes of the cut-statistic cache per party and
    the HBM bytes one party-A local-update sample moves, per cache dtype
    and sample path (analytic counters — ``workset.sample_hbm_bytes``)."""
    import jax.numpy as jnp
    from repro.core.workset import QUANT_KEYS, sample_hbm_bytes, \
        workset_init, workset_nbytes

    W, B, F = 5, 4096, 256
    z = jnp.zeros((B, F), jnp.float32)
    entry = {"z": z, "dz": z}
    csv_row("# workset cache roofline (paper geometry W=5 B=4096 z=256; "
            "per party)")
    csv_row("cache_dtype", "cache_MB", "sample_hbm_KB_unfused",
            "sample_hbm_KB_fused")
    for cd in ("float32", "bfloat16", "int8"):
        nb = workset_nbytes(workset_init(W, entry, cache_dtype=cd),
                            QUANT_KEYS)
        csv_row(cd, f"{nb / 1e6:.1f}",
                f"{sample_hbm_bytes(entry, cd, fused=False) / 1e3:.0f}",
                f"{sample_hbm_bytes(entry, cd, fused=True) / 1e3:.0f}")


def main():
    report_table()
    report_perf_variants()
    cache_accounting()
    pod_collective_accounting()


if __name__ == "__main__":
    main()
