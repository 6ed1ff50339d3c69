"""Fleet runner: hundreds of CELU-VFL training jobs as one compiled XLA
program per cohort.

``run_fleet(configs, rounds, workload=...)`` takes a list of
:class:`JobSpec` (one per job), groups them into COHORTS by their static
knobs (:func:`cohort_key` — depth, codec, cache dtype, W/R, sampling,
optimizer... anything that changes the traced program), and runs each
cohort as ONE ``jit(lax.scan(step-over-rounds) + flush)`` with the job
axis batched:

  * ``mode="vmap"`` (default) vectorizes the job axis — maximum
    throughput; jobs in a cohort share every op.  A fleet of ONE job is
    bit-identical to the scalar engine (the N=1 golden gate in
    tests/test_fleet.py); at N > 1 the lanes are bit-identical to EACH
    OTHER, but CPU XLA's batched GEMMs may reassociate reductions a ULP
    away from the unbatched program (docs/FLEET.md has the full story).
  * ``mode="map"`` lowers the job axis with ``lax.map`` — lanes execute
    the UNBATCHED program sequentially inside the same single compiled
    call, bit-identical to the scalar engine at ANY fleet size (the N=3
    golden gate).  Host-dispatch savings are identical; vector-unit
    sharing across jobs is given up.

Traced per-job knobs (lr, rng seed, xi threshold) batch freely inside a
cohort via :class:`~repro.fleet.scheduler.JobHyper`; every job shares the
cohort's batch schedule (the sweep-grid / hyper-fleet regime — jobs that
need their own DATA belong in their own cohort).  ``shard=True`` splits
the job axis over the host's device grid (``launch.mesh.make_fleet_mesh``)
— on CPU CI a multi-device grid comes from
``--xla_force_host_platform_device_count`` in a fresh process'
environment.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import CELUConfig
from ..core import engine
from ..optim import make_optimizer
from .scheduler import JobHyper, average_flush_metrics, make_fleet_step


@dataclasses.dataclass(frozen=True)
class JobSpec:
    """One fleet job.  ``celu`` carries the static engine knobs; ``lr``,
    ``seed`` and ``xi_degrees`` are TRACED per-job values (same compiled
    cohort); ``optimizer``/``local_steps``/``compression``/``depth`` are
    static and partition cohorts.  ``seed`` drives both the param init
    (``workload.params_for``) and the engine rng chain (seed 0 = the
    scalar engine's golden-pinned chain)."""
    celu: CELUConfig
    lr: float = 0.05
    seed: int = 0
    optimizer: str = "adagrad"
    local_steps: int = -1
    compression: Optional[str] = None
    depth: Optional[int] = None
    xi_degrees: Optional[float] = None

    def resolved_depth(self) -> int:
        return self.celu.pipeline_depth if self.depth is None else self.depth

    def resolved_xi(self) -> float:
        return self.celu.xi_degrees if self.xi_degrees is None \
            else self.xi_degrees


def cohort_key(spec: JobSpec):
    """Static partition key: two jobs trace the same program iff their
    keys match.  ``xi_degrees`` is normalized OUT of the celu config (it
    is traced via JobHyper); everything else in the config — W, R,
    sampling, weighting, wire/cache dtypes, codec, depth, damping — is
    compile-time structure."""
    celu = dataclasses.replace(spec.celu, xi_degrees=0.0)
    return (celu, spec.optimizer, spec.local_steps, spec.compression,
            spec.resolved_depth())


class FleetWorkload(NamedTuple):
    """What every job in the fleet trains on.  ``params_for(seed)`` builds
    one job's initial params ``{"a": [...], "b": ...}``;
    ``batch_stream()`` returns a fresh iterator of
    ``(batch_idx, batches_a, batch_b)`` — the schedule is stacked once
    and shared by the whole fleet."""
    task: engine.KPartyTask
    params_for: Callable[[int], Dict[str, Any]]
    batch_stream: Callable[[], Any]


@dataclasses.dataclass
class FleetResult:
    """Per-job stacked outcomes, rows in the caller's ``configs`` order.
    Warmup rounds of depth >= 2 jobs report NaN in ``losses`` (exactly the
    scalar pipeline's warmup rows)."""
    losses: np.ndarray            # (n_jobs, rounds) f32
    w_mean: np.ndarray            # (n_jobs, rounds) f32
    w_zero_frac: np.ndarray       # (n_jobs, rounds) f32
    local_steps: np.ndarray       # (n_jobs, rounds) int32
    flush_metrics: Dict[str, np.ndarray]   # each (n_jobs,)
    comm_rounds: np.ndarray       # (n_jobs,) int32, queue drained
    steps_a: List[List[int]]      # per job, one counter per party A_i
    steps_b: np.ndarray           # (n_jobs,) int64
    round_wire_bytes: np.ndarray  # (n_jobs,) exact wire bytes per round
    wall_s: float                 # device wall across cohorts (post-compile)
    compile_s: float              # trace+compile wall across cohorts
    n_cohorts: int
    cohort_sizes: List[int]
    mode: str
    _final: List[Dict[str, Any]] = dataclasses.field(default_factory=list)

    def final_state(self, j: int) -> Dict[str, Any]:
        """Job ``j``'s final engine state dict (numpy leaves) — feed its
        params to eval (AUC etc.)."""
        return self._final[j]


def _stack(trees: Sequence[Any]):
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)


def _unstack(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _stack_batches(workload: FleetWorkload, rounds: int):
    it = workload.batch_stream()
    bis, bas, bbs = [], [], []
    for _ in range(rounds):
        bi, ba, bb = next(it)
        bis.append(jnp.asarray(bi))
        bas.append(ba)
        bbs.append(bb)
    return _stack(bis), _stack(bas), _stack(bbs)


def run_fleet(configs: Sequence[JobSpec], rounds: int, *,
              workload: FleetWorkload, mode: str = "vmap",
              shard: bool = False, mesh=None) -> FleetResult:
    """Train every job for ``rounds`` communication rounds (plus the
    queue drain) and return stacked per-job metrics.  One compiled XLA
    program per cohort; see the module docstring for ``mode``/``shard``."""
    if mode not in ("vmap", "map"):
        raise ValueError(f"mode must be 'vmap' or 'map', got {mode!r}")
    if not configs:
        raise ValueError("empty fleet")
    n_jobs = len(configs)
    bis, bas, bbs = _stack_batches(workload, rounds)
    ex_ba = _unstack(bas, 0)
    ex_bb = _unstack(bbs, 0)

    # partition into cohorts, preserving first-seen order
    cohorts: Dict[Any, List[int]] = {}
    for j, spec in enumerate(configs):
        cohorts.setdefault(cohort_key(spec), []).append(j)

    losses = np.full((n_jobs, rounds), np.nan, np.float32)
    w_mean = np.zeros((n_jobs, rounds), np.float32)
    w_zero = np.zeros((n_jobs, rounds), np.float32)
    lsteps = np.zeros((n_jobs, rounds), np.int32)
    flush_m = {"local_steps": np.zeros(n_jobs, np.int32),
               "w_mean": np.zeros(n_jobs, np.float32),
               "w_zero_frac": np.zeros(n_jobs, np.float32)}
    commr = np.zeros(n_jobs, np.int32)
    steps_a: List[List[int]] = [[] for _ in range(n_jobs)]
    steps_b = np.zeros(n_jobs, np.int64)
    rbytes = np.zeros(n_jobs, np.int64)
    finals: List[Dict[str, Any]] = [{} for _ in range(n_jobs)]

    wall = 0.0
    compile_wall = 0.0
    for jobs in cohorts.values():
        spec0 = configs[jobs[0]]
        celu, depth = spec0.celu, spec0.resolved_depth()
        tp = engine.make_transport(celu, spec0.compression)
        init_fn, step_fn, flush_fn = make_fleet_step(
            workload.task, celu, depth=depth, optimizer=spec0.optimizer,
            local_steps=spec0.local_steps, transport=tp)

        # per-job scalar init, stacked over the cohort's job axis
        fstates, hypers = [], []
        z_shapes = None
        for j in jobs:
            spec = configs[j]
            params = workload.params_for(spec.seed)
            if z_shapes is None:
                z_shapes = [jax.eval_shape(workload.task.forward_a, p, b)
                            for p, b in zip(params["a"], ex_ba)]
                if workload.task.counted:
                    z_shapes = [z for z, _ in z_shapes]
            opt = make_optimizer(spec.optimizer, spec.lr)
            state = engine.init_state(workload.task, params, opt, celu,
                                      ex_ba, ex_bb, transport=tp)
            fstates.append(init_fn(state, ex_ba, ex_bb))
            hypers.append(JobHyper.for_spec(spec.lr, spec.resolved_xi(),
                                            spec.seed))
        fs = _stack(fstates)
        hyper = _stack(hypers)
        per_round = tp.round_bytes([z.shape for z in z_shapes])

        if mode == "vmap":
            step_v = jax.vmap(step_fn, in_axes=(0, 0, None, None, None))
            flush_v = jax.vmap(flush_fn, in_axes=(0, 0))
        else:
            def step_v(fs, hyper, ba, bb, bi, _step=step_fn):
                return jax.lax.map(
                    lambda args: _step(args[0], args[1], ba, bb, bi),
                    (fs, hyper))

            def flush_v(fs, hyper, _flush=flush_fn):
                return jax.lax.map(lambda args: _flush(args[0], args[1]),
                                   (fs, hyper))

        def run(fs, hyper, bis, bas, bbs, _step=step_v, _flush=flush_v):
            def one(carry, xs):
                bi, ba, bb = xs
                carry, m = _step(carry, hyper, ba, bb, bi)
                return carry, m
            fs, ms = jax.lax.scan(one, fs, (bis, bas, bbs))
            fs, fm = _flush(fs, hyper)
            return fs, ms, fm

        if shard:
            from ..launch.mesh import fleet_job_sharding, make_fleet_mesh
            m_ = mesh if mesh is not None else make_fleet_mesh()
            ndev = int(m_.devices.size)
            if len(jobs) % ndev != 0:
                raise ValueError(
                    f"cohort of {len(jobs)} jobs does not divide the "
                    f"{ndev}-device fleet mesh — pad the sweep or pass "
                    f"shard=False")
            sharding = fleet_job_sharding(m_)
            fs = jax.device_put(fs, sharding)
            hyper = jax.device_put(hyper, sharding)

        t0 = time.perf_counter()
        compiled = jax.jit(run).lower(fs, hyper, bis, bas, bbs).compile()
        compile_wall += time.perf_counter() - t0
        t0 = time.perf_counter()
        fs, ms, fm = compiled(fs, hyper, bis, bas, bbs)
        jax.block_until_ready((fs, ms, fm))
        wall += time.perf_counter() - t0

        # scatter cohort lanes back into caller order
        for lane, j in enumerate(jobs):
            losses[j] = np.asarray(ms["loss"][:, lane])
            w_mean[j] = np.asarray(ms["w_mean"][:, lane])
            w_zero[j] = np.asarray(ms["w_zero_frac"][:, lane])
            lsteps[j] = np.asarray(ms["local_steps"][:, lane])
            lane_fm = average_flush_metrics(_unstack(fm, lane))
            for k in flush_m:
                flush_m[k][j] = np.asarray(lane_fm[k])
            st = _unstack(fs.state, lane)
            commr[j] = int(st["comm_rounds"])
            steps_a[j] = [int(s) for s in st["steps"]["a"]]
            steps_b[j] = int(st["steps"]["b"])
            rbytes[j] = per_round
            finals[j] = jax.tree_util.tree_map(np.asarray, st)

    return FleetResult(
        losses=losses, w_mean=w_mean, w_zero_frac=w_zero,
        local_steps=lsteps, flush_metrics=flush_m, comm_rounds=commr,
        steps_a=steps_a, steps_b=steps_b, round_wire_bytes=rbytes,
        wall_s=wall, compile_s=compile_wall, n_cohorts=len(cohorts),
        cohort_sizes=[len(v) for v in cohorts.values()], mode=mode,
        _final=finals)
