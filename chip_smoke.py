"""Run CELU-VFL training on a TPU through the normal entry points.

One chip (no arguments), each phase in this one process:

  wdl_fp32    wdl-criteo at its full config and the paper's message
              geometry (B=4096, R=5, W=5, xi=60 deg), fp32 workset cache,
              pipeline depth 0, through ``repro.launch.train.main``;
  wdl_int8    the same with the int8 cache (SR quantizer on insert, the
              int8 fused sample on every local update);
  smollm_int8 smollm-360m at full published width (32 layers, d=960,
              vocab 49152), B=8, S=512, int8 cache and int8 AdaGrad state.

For each phase the training entry point compiles the round program before
the first round; this script reads that compiled program and requires a
Mosaic kernel (``tpu_custom_call``) for every kernel the phase must use,
so a silent fall back to a jnp reference fails the run.  Losses must be
finite, and for wdl-criteo the last below the first (three rounds of the
split LLM on the synthetic bigram stream stay near ln(vocab) and need
not fall).

``--chips 4`` runs only the two-party pod round (``make_pod_round``):
Party A on chip 0, Party B on chip 1, pipeline depths 0 and 1, compared
round by round with the same rounds from the same seed on a two-device
host-CPU mesh in this process.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed
on a TPU; on any other platform the script exits non-zero first.
``--rehearse`` runs the same control flow at tiny sizes on the CPU (the
Pallas kernels interpreted) and never reports ok.

    python chip_smoke.py [--chips 4] [--rehearse]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# kernels each phase's round program must contain as Mosaic calls
WDL_FP32_KERNELS = ("fused_sample",)
WDL_INT8_KERNELS = ("fused_sample_q8", "quantize_sr")
# (the int8 AdaGrad state steps through its jnp reference unless the
# optimizer is built with use_pallas=True, which the CLI does not do)
LLM_INT8_KERNELS = ("fused_sample_q8", "quantize_sr")

# pod round at the paper's message geometry (the demo WDL model's towers
# are two-layer: 26 fields padded on Party B's 13, hidden 512, z 256)
POD = dict(n_fields=26, vocab=1024, embed_dim=16, z_dim=256, hidden=512,
           batch=4096, W=5, R=5, rounds=4, lr=0.001)
# chip and host CPU differ in transcendental and reduction rounding at
# the float32 ulp level (matmuls run at float32 precision on both); a few
# AdaGrad rounds keep that far below this bound on the loss
POD_LOSS_ATOL = 1e-3


def _kernels_in(text: str):
    """Names of the Pallas kernels compiled as Mosaic custom calls."""
    names = set()
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'op_name="[^"]*?([A-Za-z0-9_]+)/pallas_call"',
                          line)
            if m:
                names.add(m.group(1))
    return sorted(names)


def _device():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _check(cond: bool, what: str):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _train_phase(name, argv, kernels, *, rehearse, must_fall):
    from repro.launch import train
    t0 = time.perf_counter()
    out = train.main(argv)
    wall = time.perf_counter() - t0
    if "history" in out:
        losses = [h[1] for h in out["history"]]
    else:
        losses = out["losses"]
    found = _kernels_in(out["round"].as_text())
    dev = _device()
    print(json.dumps({
        "phase": name, "platform": dev["platform"], "kind": dev["kind"],
        "compile_s": out["compile_s"], "wall_s": wall, "losses": losses,
        "kernels": found, "peak_bytes_in_use": _peak_bytes()}), flush=True)
    _check(len(losses) > 0 and all(map(math.isfinite, losses)),
           f"{name}: non-finite losses {losses}")
    if must_fall:
        _check(losses[-1] < losses[0],
               f"{name}: loss did not fall ({losses[0]} -> {losses[-1]})")
    if not rehearse:
        missing = [k for k in kernels if k not in found]
        _check(not missing, f"{name}: no Mosaic call for {missing} in the "
               f"compiled round (found {found})")


def one_chip(rehearse: bool):
    wdl = ["--arch", "wdl-criteo", "--protocol", "celu", "--R", "5",
           "--W", "5", "--xi", "60", "--pipeline-depth", "0", "--seed", "0"]
    if rehearse:
        wdl += ["--small", "--rounds", "4", "--batch-size", "256",
                "--n-train", "2048", "--n-test", "512", "--lr", "0.001"]
    else:
        wdl += ["--rounds", "12", "--batch-size", "4096",
                "--n-train", "32768", "--n-test", "8192", "--lr", "0.001"]
    # tiny rehearsal runs are too short to fall
    _train_phase("wdl_fp32", wdl + ["--cache-dtype", "float32"],
                 WDL_FP32_KERNELS, rehearse=rehearse, must_fall=not rehearse)
    _train_phase("wdl_int8", wdl + ["--cache-dtype", "int8"],
                 WDL_INT8_KERNELS, rehearse=rehearse, must_fall=not rehearse)
    llm = ["--arch", "smollm-360m", "--protocol", "celu", "--R", "5",
           "--W", "5", "--pipeline-depth", "0", "--cache-dtype", "int8",
           "--opt-state-dtype", "int8", "--seed", "0", "--rounds", "3",
           "--lr", "0.001"]
    if rehearse:
        llm += ["--reduced", "--batch-size", "2", "--seq-len", "16"]
    else:
        llm += ["--batch-size", "8", "--seq-len", "512"]
    _train_phase("smollm_int8", llm, LLM_INT8_KERNELS, rehearse=rehearse,
                 must_fall=False)


def _pod_inputs(cfg, seed):
    """Host (numpy) initial pod state and per-round batches, built once so
    both meshes start from identical values."""
    import jax
    import numpy as np

    from repro.core.pod_protocol import init_pod_state
    from repro.data import synthetic as synth
    from repro.optim import adagrad

    opt = adagrad(cfg["lr"])
    with jax.default_device(jax.devices("cpu")[0]):
        state = init_pod_state(jax.random.PRNGKey(seed), None, opt,
                               n_fields=cfg["n_fields"], vocab=cfg["vocab"],
                               batch=cfg["batch"], W=cfg["W"],
                               embed_dim=cfg["embed_dim"],
                               z_dim=cfg["z_dim"], hidden=cfg["hidden"])
        state = jax.device_get(state)
    spec = dataclasses.replace(synth.TABULAR_SPECS["criteo"],
                               vocab=cfg["vocab"],
                               n_train=cfg["batch"] * cfg["rounds"],
                               n_test=8)
    data = synth.make_tabular(spec, seed=seed)
    it = synth.aligned_batches(data["train"], cfg["batch"], seed=seed)
    batches = []
    for _ in range(cfg["rounds"]):
        _, ba, bb = next(it)
        F = cfg["n_fields"]
        xb = np.zeros_like(ba["x_a"])[:, :F]
        xb[:, :bb["x_b"].shape[1]] = bb["x_b"]      # dead pad fields = 0
        x = np.stack([ba["x_a"][:, :F], xb]).astype(np.int32)
        y = np.stack([np.zeros_like(bb["y"]), bb["y"]]).astype(np.float32)
        batches.append((x, y))
    return opt, state, batches


def _pod_run(mesh, opt, state, batches, cfg, depth):
    """-> (per-round Party-B losses, compiled round text)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.pod_protocol import make_pod_round

    put = NamedSharding(mesh, P("pod"))
    params, opt_state, ws = jax.device_put(state, put)
    rnd = make_pod_round(mesh, opt, R=cfg["R"], cos_xi=0.5,
                         pipeline_depth=depth)
    x0, y0 = (jax.device_put(a, put) for a in batches[0])
    text = rnd.lower(params, opt_state, ws, x0, y0).compile().as_text()
    losses = []
    for x, y in batches:
        params, opt_state, ws, loss = rnd(params, opt_state, ws,
                                          jax.device_put(x, put),
                                          jax.device_put(y, put))
        losses.append(float(jax.device_get(loss)[1]))
    return losses, text


def four_chips(rehearse: bool):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    cfg = dict(POD)
    if rehearse:
        cfg.update(n_fields=13, vocab=64, z_dim=16, hidden=32, batch=128,
                   W=3, R=2)
    opt, state, batches = _pod_inputs(cfg, seed=0)
    chips = jax.devices()[:2]
    host = jax.devices("cpu")[-2:]
    _check(len(chips) == 2 and len(host) == 2,
           f"need 2 accelerator and 2 host devices, have {jax.devices()} "
           f"and {jax.devices('cpu')}")
    dev = _device()
    for depth in (0, 1):
        with jax.default_matmul_precision("float32"):
            t0 = time.perf_counter()
            got, text = _pod_run(Mesh(np.array(chips), ("pod",)), opt,
                                 state, batches, cfg, depth)
            wall = time.perf_counter() - t0
            want, _ = _pod_run(Mesh(np.array(host), ("pod",)), opt, state,
                               batches, cfg, depth)
        diff = max(abs(a - b) for a, b in zip(got, want))
        print(json.dumps({
            "phase": f"pod_round_depth{depth}", "platform": dev["platform"],
            "kind": dev["kind"], "devices": [str(d) for d in chips],
            "wall_s": wall, "losses": got, "losses_host_cpu": want,
            "max_abs_diff": diff, "atol": POD_LOSS_ATOL,
            "collective_permute": "collective-permute" in text,
            "peak_bytes_in_use": _peak_bytes()}), flush=True)
        _check(all(map(math.isfinite, got)),
               f"depth {depth}: non-finite losses {got}")
        _check(diff <= POD_LOSS_ATOL,
               f"depth {depth}: chip and host-CPU losses differ by {diff}")
        _check("collective-permute" in text,
               f"depth {depth}: no collective-permute in the pod round")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never reports ok")
    args = ap.parse_args(argv)

    if args.chips == 4:
        # the reference mesh: two host-CPU devices in this same process
        # (the flag sizes only the host platform, never the chips)
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count"
                                   "=4").strip()
        if os.environ.get("JAX_PLATFORMS") == "tpu":
            os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    import jax

    from repro.launch import compile_cache
    compile_cache.enable()

    dev = _device()
    if not args.rehearse:
        _check(dev["platform"] == "tpu",
               f"no TPU: JAX found {jax.devices()}")
        if args.chips == 4:
            _check(dev["count"] >= 4, f"--chips 4 needs 4 chips, JAX "
                   f"found {dev['count']}")
    if args.chips == 4:
        four_chips(args.rehearse)
    else:
        one_chip(args.rehearse)
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": dev}))
        return
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
