"""The paper's technique at mesh level: a 2-pod CELU round where Party A
lives on pod 0 and Party B on pod 1, the cut-tensor exchange is the
engine's ``PodTransport`` (a ``ppermute`` pair over the ``pod`` axis), and
local updates hit the device-resident workset table (zero inter-pod
traffic).  The round itself is the same K-party engine logic as the
host-sim protocols — only the transport differs.

Runs in one process on the first two devices of the default backend: two
chips on a TPU host, or two host-CPU devices (the script sizes the host
platform to two devices before JAX starts).  Prints the training losses
and the measured inter-pod bytes per model update for R ∈ {0, 5}.

    PYTHONPATH=src python examples/pod_protocol_demo.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.engine import PodTransport
    from repro.core.pod_protocol import init_pod_state, make_pod_round
    from repro.launch.dryrun import collective_bytes
    from repro.optim import adagrad

    devices = jax.devices()[:2]
    if len(devices) < 2:
        raise SystemExit(f"the 2-pod demo needs two devices, JAX found "
                         f"{jax.devices()}")
    mesh = Mesh(np.array(devices), ("pod",))
    opt = adagrad(0.05)

    # --- train a few rounds -----------------------------------------------
    params, opt_state, ws = init_pod_state(jax.random.PRNGKey(0), mesh, opt,
                                           n_fields=8, vocab=64, batch=128,
                                           W=3, z_dim=16, hidden=32)
    rnd = make_pod_round(mesh, opt, R=3, cos_xi=0.5,
                         transport=PodTransport(axis="pod"))
    rng = np.random.default_rng(0)
    teacher = rng.normal(size=(16, 64)).astype(np.float32)
    print(f"2-pod CELU round (R=3, W=3) on {[str(d) for d in devices]}:")
    for i in range(20):
        x = rng.integers(0, 64, size=(2, 128, 8), dtype=np.int32)
        logit = teacher[np.arange(16)[None, :],
                        x.transpose(1, 0, 2).reshape(128, 16)].sum(1) / 4.0
        y = np.stack([np.zeros(128, np.float32),
                      (rng.random(128) < 1 / (1 + np.exp(-logit))
                       ).astype(np.float32)])
        params, opt_state, ws, loss = rnd(params, opt_state, ws,
                                          jnp.asarray(x), jnp.asarray(y))
        if (i + 1) % 5 == 0:
            print(f"  round {i+1:2d}  Party-B loss {float(loss[1]):.4f}")

    # --- inter-pod bytes per update ---------------------------------------
    print("inter-pod ppermute bytes per model update (B=4096, z=256):")
    for R in (0, 5):
        p, o, w = init_pod_state(jax.random.PRNGKey(0), mesh, opt,
                                 n_fields=16, vocab=512, batch=4096, W=5,
                                 z_dim=256, hidden=256)
        r = make_pod_round(mesh, opt, R=max(R, 1), cos_xi=0.5)
        x = jax.ShapeDtypeStruct((2, 4096, 16), jnp.int32)
        y = jax.ShapeDtypeStruct((2, 4096), jnp.float32)
        txt = r.lower(p, o, w, x, y).compile().as_text()
        cp = collective_bytes(txt)["collective-permute"]
        ups = 1 + R
        print(f"  R={R}: {cp/1e6:.2f} MB/round, {ups} updates "
              f"-> {cp/ups/1e6:.2f} MB/update")


if __name__ == "__main__":
    # two host-CPU devices when no accelerator provides two (the flag
    # sizes only the host platform); set before JAX starts
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2"
                               ).strip()
    main()
