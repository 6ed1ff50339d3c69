"""Wide & Deep split at the party boundary (the CELU-VFL paper's Criteo
model): the program under test, its weights, and its plain reference.

Party A embeds its categorical fields and runs its MLP tower to the cut
activation Z_A.  Party B runs its own tower on its fields, concatenates
[Z_A, Z_B] into the top MLP, adds the wide term (one learned scalar per
(field, value) of its fields) and a bias, and takes the logistic loss
per row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

GENERATOR = "tabular"


def data_params(cfg, job):
    return {"fields_a": cfg["fields_a"], "fields_b": cfg["fields_b"],
            "vocab": cfg["vocab"], "batch": job["batch"]}


def train_flops_per_row(cfg, job):
    """FLOPs of one forward + backward pass per training row: 6 per
    matmul weight (``flops.dlrm_wdl_weights``); gathers not counted."""
    import flops
    return 6.0 * flops.dlrm_wdl_weights(cfg)


def program(cfg):
    """The program's two-party task and the shapes of its parameters."""
    from repro.models.tabular import DLRMConfig, make_dlrm
    dc = DLRMConfig(model="wdl", fields_a=cfg["fields_a"],
                    fields_b=cfg["fields_b"], vocab=cfg["vocab"],
                    embed_dim=cfg["embed_dim"], z_dim=cfg["z_dim"],
                    hidden=tuple(cfg["hidden"]))
    init_fn, task, _ = make_dlrm(dc)
    return task, jax.eval_shape(lambda k: init_fn(k, dc),
                                 jax.random.PRNGKey(0))


def _mlp(key, dims):
    ks = jax.random.split(key, len(dims) - 1)
    out = []
    for k, i, o in zip(ks, dims[:-1], dims[1:]):
        lim = 1.0 / jnp.sqrt(jnp.float32(i))
        out.append({"w": jax.random.uniform(k, (i, o), jnp.float32, -lim, lim),
                    "b": jnp.zeros((o,), jnp.float32)})
    return out


def _tower(key, cfg, fields):
    ke, km = jax.random.split(key)
    E = cfg["embed_dim"]
    return {"embed": jax.random.normal(ke, (fields, cfg["vocab"], E),
                                       jnp.float32) * 0.01,
            "mlp": _mlp(km, [fields * E, *cfg["hidden"], cfg["z_dim"]])}


def make_weights(cfg, key):
    """Initial weights in the program's layout (traced inside one jit):
    embeddings N(0, 0.01^2), dense weights U(+-1/sqrt(fan_in)), zero
    biases, wide weights N(0, 0.01^2)."""
    ka, kb, kt, kw = jax.random.split(key, 4)
    z, h = cfg["z_dim"], cfg["hidden"]
    return {"a": {"tower": _tower(ka, cfg, cfg["fields_a"])},
            "b": {"tower": _tower(kb, cfg, cfg["fields_b"]),
                  "top": _mlp(kt, [2 * z, h[-1], 1]),
                  "wide": jax.random.normal(
                      kw, (cfg["fields_b"], cfg["vocab"]),
                      jnp.float32) * 0.01,
                  "bias": jnp.zeros((), jnp.float32)}}


# ------------------------------------------------------------- reference
def _ref_mlp(layers, x, P):
    for i, layer in enumerate(layers):
        x = P.dot("bi,io->bo", x, layer["w"]) + P.cast(layer["b"])
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def _ref_tower(tower, x):
    B, F = x.shape
    e = tower["embed"][jnp.arange(F)[None, :], x]
    return e.reshape(B, -1)


def reference(cfg):
    """-> (forward_a(pa, batch_a, P), loss_b(pb, z, batch_b, P))."""
    def forward_a(pa, batch_a, P):
        t = pa["tower"]
        return _ref_mlp(t["mlp"], P.cast(_ref_tower(t, batch_a["x_a"])), P)

    def loss_b(pb, z, batch_b, P):
        t = pb["tower"]
        zb = _ref_mlp(t["mlp"], P.cast(_ref_tower(t, batch_b["x_b"])), P)
        h = jnp.concatenate([P.cast(z), zb], axis=-1)
        logit = _ref_mlp(pb["top"], h, P)[:, 0].astype(jnp.float32)
        xb = batch_b["x_b"]
        wide = pb["wide"][jnp.arange(xb.shape[1])[None, :], xb].sum(axis=1)
        logit = logit + wide + pb["bias"]
        y = batch_b["y"]
        return (jnp.maximum(logit, 0) - logit * y
                + jnp.log1p(jnp.exp(-jnp.abs(logit))))

    return forward_a, loss_b
