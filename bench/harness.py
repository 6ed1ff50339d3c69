"""The cell benchmark: one run of one cell, from the seed to a result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

What one run does, in order:

1. finds the cell in ``BENCHMARK.json``, its configuration file, its
   traffic file (``bench/traffic/<traffic>.json``: the driver, the job
   and the data parameters) and its limits (``bench/workloads/<cell>.json``);
2. refuses to go on without a TPU and as many chips as the cell asks for,
   after printing the platform, device kind and count on standard error;
3. set-up, timed as ``setup_s``: the driver builds the program's entry
   from the configuration, makes the weights on the device from the seed,
   compiles (JAX's persistent cache lives at ``<checkout>/.jax_cache``),
   drives the compiled step through its first steps, which the reference
   will follow, and a few warm-up steps;
4. the window: the driver's step, timed on the host, for ``--seconds``;
   with ``--trace 1`` a short traced window follows it;
5. reads the peak device memory, frees the program's state, runs the
   driver's plain reference over the first steps, and compares.  Each
   number compared is printed beside its limit on standard error, and
   the result line ends with them under ``checks``.

Every metric is the output of a reader ``bench/metrics/<name>.py``
(``read(rec) -> value or None``), chosen by the entries of
``BENCHMARK.json``.  Exceptions are not caught: a failing run ends with
its traceback and a non-zero exit, and prints no result line.

``--rehearse`` runs the same flow on the CPU at the tiny sizes that the
configuration and traffic files give under ``rehearse``; its last line
says ``"rehearsal": true`` and carries no result keys.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, Optional

import check
import flops

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
OUT_DIR = os.path.join(ROOT, ".bench_out")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def merge(base: Dict, over: Dict) -> Dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


class Cell:
    """Everything one cell's files say, with the rehearsal overrides
    applied when asked for."""

    def __init__(self, name: str, rehearse: bool = False,
                 root: str = ROOT):
        self.root = root
        bench = read_json(os.path.join(root, "BENCHMARK.json"))
        ws = {w["name"]: w for w in bench["workloads"]}
        if name not in ws:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(ws)})")
        self.name, self.entry, self.bench = name, ws[name], bench
        cfgs = {c["name"]: c for c in bench["configs"]}
        cfg = read_json(os.path.join(root, cfgs[self.entry["config"]]
                                     ["file"]))
        traffic = read_json(os.path.join(
            root, "bench", "traffic", self.entry["traffic"] + ".json"))
        if rehearse:
            cfg = merge(cfg, cfg.get("rehearse", {}))
            traffic = merge(traffic, traffic.get("rehearse", {}))
        self.cfg, self.traffic = cfg, traffic
        self.job = traffic["job"]
        self.chips = self.entry["chips"]
        lim = os.path.join(root, "bench", "workloads", name + ".json")
        self.limits = read_json(lim) if os.path.exists(lim) else {}
        self.family = load_module(
            os.path.join(root, "bench", "families", cfg["family"] + ".py"),
            "family_" + cfg["family"].replace("-", "_"))
        self.driver = load_module(
            os.path.join(root, "bench", "drivers", traffic["driver"] + ".py"),
            "driver_" + traffic["driver"])

    def data_spec(self) -> Dict:
        spec = {"generator": self.family.GENERATOR}
        spec.update(self.family.data_params(self.cfg, self.job))
        spec.update(self.traffic.get("data", {}))
        return spec

    def metrics(self, trace: bool):
        """The entries of the metrics this cell reports in this run."""
        key = "per_layer" if trace else "end_to_end"
        e2e = [m["name"] for m in self.bench["end_to_end"]
               if self.name in m.get("workloads", [self.name])]
        out = []
        for m in self.bench[key]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif not trace or m["moves"] in e2e:
                out.append(m)
        return out


# ------------------------------------------------------------------ device
def configure_jax(rehearse: bool, chips: int = 1):
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={chips}"
            ).strip()
    import jax
    if rehearse:                 # nothing a chip run could read back
        return jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def device_info(jax) -> Dict[str, Any]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def say(*a):
    print(*a, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts backend compilations (``jax.monitoring`` events)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ------------------------------------------------------------------ window
def run_window(run, seconds: float) -> Dict[str, Any]:
    """Step until ``seconds`` have passed.  Every step ends when its
    outputs are complete: the window is all the work and all the time."""
    import jax
    losses, prep = [], 0.0
    t0 = time.perf_counter()
    while True:
        loss, p = run.step()
        losses.append(loss)
        prep += p
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    vals = [float(v) for v in jax.device_get(losses)]
    n = len(vals)
    return {"seconds": elapsed, "steps": n, "rows": n * run.rows_per_step,
            "tokens": n * run.tokens_per_step if run.tokens_per_step
            else None, "prep_s": prep,
            "failed": sum(not math.isfinite(v) for v in vals)}


def run_traced(run, seconds: float, min_steps: int, tdir: str) -> int:
    """A short window under the profiler, bounded by a ``window`` span.
    -> steps taken."""
    import jax
    shutil.rmtree(tdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0          # the harness's spans suffice
    jax.profiler.start_trace(tdir, profiler_options=options)
    n, t0 = 0, time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while n < min_steps or time.perf_counter() - t0 < seconds:
            run.step(annotate=True)
            n += 1
    jax.profiler.stop_trace()
    return n


# -------------------------------------------------------------------- main
def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no result line")
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None, fault: str = ""):
    """One run.  -> the result line's dict (printed by ``run.py``).
    ``fault`` plants a fault in the timed path (the tests' use)."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = Cell(args.workload, rehearse=args.rehearse)
    jax = configure_jax(args.rehearse, cell.chips)
    dev = device_info(jax)
    say(f"[bench] platform {dev['platform']} kind {dev['kind']} count "
        f"{dev['count']}; cell {cell.name} seed {args.seed} seconds "
        f"{args.seconds} trace {args.trace}")
    if not args.rehearse:
        if dev["platform"] != "tpu":
            raise SystemExit(f"bench: no TPU: JAX found {jax.devices()}")
        if dev["count"] < cell.chips:
            raise SystemExit(f"bench: {cell.name} needs {cell.chips} chips, "
                             f"JAX found {dev['count']}")
    sys.path.insert(0, os.path.join(cell.root, "src"))
    T = load_module(os.path.join(BENCH, "trace.py"), "bench_trace")
    compiles = CompileCounter()

    run = cell.driver.Run(cell, args.seed, fault=fault)
    setup_s = time.perf_counter() - t_start
    say(f"[bench] setup {setup_s:.3f} s")
    c0 = compiles.n
    win = run_window(run, args.seconds)
    in_window = compiles.n - c0
    say(f"[bench] window {win['steps']} steps in {win['seconds']:.3f} s, "
        f"{in_window} compiles")
    trace, traced_steps = None, 0
    if args.trace:
        tdir = os.path.join(OUT_DIR, "trace", cell.name)
        traced_steps = run_traced(run, cell.traffic.get("trace_seconds", 2),
                                  cell.traffic.get("trace_min_steps", 3),
                                  tdir)
        trace = T.load(tdir)
        hlo = run.hlo_text()
        trace.kernels, trace.names = T.kernel_ops(hlo), T.op_names(hlo)
        shutil.rmtree(tdir, ignore_errors=True)
    mem = peak_bytes(run.devices)
    run.release()

    numbers = run.numbers()
    checks, ok = check.judge(numbers, cell.limits)
    correct = ok and win["failed"] == 0
    for k, v in numbers.items():
        if k not in checks:
            say(f"[bench] {k}: {v}")

    rec = {"cell": cell, "window": win, "setup_s": setup_s,
           "trace": trace, "traced_steps": traced_steps,
           "round_flops": flops.round_flops(cell.family, cell.cfg,
                                            cell.job),
           "mesh_devices": len(run.devices),
           "peaks": peaks_for(dev["kind"], args.rehearse)}
    metrics = read_metrics(cell, rec, bool(args.trace))
    device = dict(dev, memory_peak_bytes=mem)
    out = {"correct": correct, "attempted": win["steps"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s()
        out["breakdown"] = {"device_ops": T.top(trace.op_seconds()),
                            "idle_gaps": T.top(trace.idle_gaps_by_host())}
    out["compiles_in_window"] = in_window
    say(f"[bench] correct {correct} (failed steps {win['failed']}); "
        f"compared, each beside its limit:")
    for k, c in checks.items():
        say(f"[bench] {k} {c['value']!r} limit {c['limit']!r}")
    out["checks"] = checks
    if args.rehearse:
        return {"rehearsal": True, "device": device,
                "would_be_correct": correct, "numbers": metrics,
                "checks": checks}
    return out


def read_metrics(cell: Cell, rec: Dict, trace: bool) -> Dict:
    """Each metric of this run from its reader,
    ``bench/metrics/<name>.py``; a reader that finds nothing to read
    leaves its metric out."""
    out = {}
    for m in cell.metrics(trace):
        reader = load_module(os.path.join(cell.root, "bench", "metrics",
                                          m["name"] + ".py"),
                             "metric_" + m["name"].replace(".", "_"))
        v = reader.read(rec)
        if v is None:
            say(f"[bench] metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def peaks_for(kind: str, rehearse: bool) -> Optional[Dict]:
    """The chip's published peaks; an unknown chip is an error (a
    rehearsal on the CPU has none)."""
    table = read_json(os.path.join(BENCH, "peaks.json"))
    if kind in table:
        return table[kind]
    if rehearse:
        return None
    raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
