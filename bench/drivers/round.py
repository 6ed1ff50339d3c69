"""Driver ``round``: the program's compiled CELU round at pipeline depth 0.

Built as the training entry point (``repro.launch.train``) builds it at
depth 0: the configuration's two-party task, lifted to the engine's
K-party form, AdaGrad from ``repro.optim.make_optimizer`` (int8 state
where the job asks for it), ``CELUConfig`` and its protocol preset,
``engine.init_state`` and ``engine.make_round(..., donate=True)``,
lowered and compiled for the first batch's shapes.  Each step feeds one
fresh host batch, put on the device as the entry point does (one
``jnp.asarray`` per array), and waits for the new state: at depth 0 a
round ends at the party boundary on the host.

The compiled round, with its state, is built once.  Its first steps
(``FIRST_STEPS``, on rows that all differ) are the ones the reference
follows; warm-up steps and the window then continue the same object.
"""
from __future__ import annotations

import time
from typing import Dict

import check
import celu_ref
import precision
import traffic as traffic_gen
from driver_util import Phases, no_span, same_layout, seed_key, span

FIRST_STEPS = 3


class Run:
    def __init__(self, cell, seed: int, fault: str = ""):
        import jax

        from repro.configs.base import CELUConfig
        from repro.core import engine
        from repro.optim import make_optimizer

        self.cell, self.seed, self.fault = cell, seed, fault
        job = cell.job
        if job.get("pipeline_depth", 0) != 0:
            raise ValueError("driver 'round' runs pipeline depth 0 only")
        self.rows_per_step = job["batch"] // (2 if fault == "half_batch"
                                              else 1)
        self.tokens_per_step = self.rows_per_step * job["seq_len"] \
            if "seq_len" in job else None
        self.devices = jax.devices()[:1]
        phases = Phases()

        task, shapes = cell.family.program(cell.cfg)
        self._make = jax.jit(lambda k: cell.family.make_weights(cell.cfg, k))
        weights = self._make(seed_key(seed))
        same_layout(weights, shapes)
        jax.block_until_ready(weights)
        phases.mark("weights")

        base = CELUConfig(R=job["R"], W=job["W"], xi_degrees=job["xi"],
                          cache_dtype=job["cache_dtype"])
        celu, n_local = engine.preset_config(job["protocol"], base)
        kw = {} if job["opt_state_dtype"] == "float32" else \
            {"state_dtype": job["opt_state_dtype"]}
        opt = make_optimizer("adagrad", job["lr"], **kw)

        self.stream = traffic_gen.stream(seed, cell.data_spec())
        self.first = [next(self.stream) for _ in range(FIRST_STEPS)]
        phases.mark("data")
        etask = engine.lift_two_party(task)
        tp = engine.make_transport(celu)
        a0, b0 = self._put(self.first[0])
        state = engine.init_state(etask, engine.lift_two_party_params(
            weights), opt, celu, a0, b0, transport=tp)
        donate = fault != "frozen"
        fn = engine.make_round(etask, opt, celu, local_steps=n_local,
                               transport=tp, donate=donate)
        fn = self.compiled = fn.lower(state, a0, b0, 0).compile()
        phases.mark("compile")
        if fault == "frozen":           # a step that returns its state
            self.fn = lambda s, a, b, i: (s, fn(s, a, b, i)[1])
        else:
            self.fn = fn
        self.state = state

        # the first steps, through the window's own call and feed
        losses, grad = [], None
        for t, (bi, ba, bb) in enumerate(self.first):
            a, b = self._put((bi, ba, bb))
            self.state, m = self.fn(self.state, a, b, bi)
            losses.append(m["loss"])
            if t == 0:
                grad = jax.block_until_ready(self._grad_norms(self.state))
        # the initial weights again, made anew from the seed rather than
        # held as a copy beside the state
        change = self._change(self.state["params"],
                              self._make(seed_key(seed)))
        self.prog = {"losses": [float(v) for v in jax.device_get(losses)],
                     "grad": [float(v) for v in jax.device_get(grad)],
                     "change": [float(v) for v in jax.device_get(change)]}
        self.prog.update(self._first_cut(self.state["ws"]["a"][0],
                                         self.first[0][0]))
        del weights
        phases.mark("first_steps")
        for _ in range(cell.traffic.get("warmup_steps", 2)):
            self.step()
        phases.mark("warmup")
        phases.say()

    # ---------------------------------------------------------------- feed
    def _put(self, batch):
        import jax.numpy as jnp
        _, ba, bb = batch
        if self.fault == "half_batch":  # half of each batch left out
            n = self.rows_per_step
            ba = {k: v[:n] for k, v in ba.items()}
            bb = {k: v[:n] for k, v in bb.items()}
        return ([{k: jnp.asarray(v) for k, v in ba.items()}],
                {k: jnp.asarray(v) for k, v in bb.items()})

    @staticmethod
    def _grad_norms(state):
        import jax
        import jax.numpy as jnp
        sums = check.accum_sums(state["opt"]["a"][0]) + \
            check.accum_sums(state["opt"]["b"])
        return jax.jit(lambda s: [jnp.sqrt(x) for x in s])(sums)

    @staticmethod
    def _first_cut(ws, batch_idx) -> Dict:
        """Round 1's exchanged Z and dZ as party A's workset ring keeps
        them (zeros where no slot holds that batch), for a ring that
        stores them as they are; a quantized ring gives nothing."""
        import jax
        import numpy as np
        buf = ws["buf"]
        if not all(isinstance(buf[k], jax.Array) and
                   jax.numpy.issubdtype(buf[k].dtype, jax.numpy.floating)
                   for k in ("z", "dz")):
            return {}
        held = np.flatnonzero(np.asarray(ws["batch_idx"]) == batch_idx)
        out = {}
        for k in ("z", "dz"):
            ring = np.asarray(jax.device_get(buf[k]), np.float32)
            out[k] = ring[held[0]] if held.size else np.zeros_like(ring[0])
        return out

    @staticmethod
    def _change(params, p0):
        import jax
        return jax.jit(check.change_norms)(
            {"a": params["a"][0], "b": params["b"]}, p0)

    def step(self, annotate: bool = False):
        """One round: host prep, dispatch, wait.  -> (loss on the device,
        host prep seconds)."""
        import jax
        span_ = span if annotate else no_span
        t0 = time.perf_counter()
        with span_("host_prep"):
            batch = next(self.stream)
            a, b = self._put(batch)
        t1 = time.perf_counter()
        with span_("dispatch"):
            self.state, m = self.fn(self.state, a, b, batch[0])
        with span_("wait"):
            jax.block_until_ready(self.state)
        return m["loss"], t1 - t0

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    # ---------------------------------------------------------- reference
    def release(self):
        """Free the program's state before the reference runs."""
        import jax
        for x in jax.tree_util.tree_leaves(self.state):
            x.delete()
        self.state = self.fn = None

    def reference(self, control: bool = False, rows: int = 0) -> Dict:
        """The plain reference over the first steps (``control``: one
        precision step below the configuration's); ``rows`` > 0 keeps
        that many rows of each batch."""
        import jax
        import numpy as np
        fa, lb = self.cell.family.reference(self.cell.cfg)
        w = jax.tree_util.tree_map(lambda x: x.astype(jax.numpy.float32),
                                   self._make(seed_key(self.seed)))
        policy = (precision.control if control else precision.reference)(
            self.cell.cfg)
        with jax.default_matmul_precision("highest"):
            out = celu_ref.run(fa, lb, w, self.first, self.cell.job,
                               policy, rows=rows)
        change = jax.jit(check.change_norms)(out["params"], w)
        return {"losses": out["losses"],
                "z": np.asarray(jax.device_get(out["z1"]), np.float32),
                "dz": np.asarray(jax.device_get(out["dz1"]), np.float32),
                "grad": [float(v) for v in jax.device_get(out["grad1"])],
                "change": [float(v) for v in jax.device_get(change)]}

    def numbers(self, ref: Dict = None, prog: Dict = None) -> Dict:
        """The compared numbers: the program's first steps (or ``prog``,
        readings of something put in its place) against the float32
        reference (or ``ref``, already run)."""
        ref = self.reference() if ref is None else ref
        import jax
        names = check.leaf_paths(jax.eval_shape(self._make,
                                                seed_key(self.seed)))
        return check.gaps(self.prog if prog is None else prog, ref, names)
