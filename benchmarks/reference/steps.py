"""The reference's first training steps: a configuration's plain
``loss_and_grads`` driven through a plain optimizer, and the numbers the
comparison reads from them. Imports nothing of ``horovod_tpu`` or optax.

``precision`` and ``fault`` exist for the control and the planted faults
(``tools/readings.py``, the tests); a benchmark run uses neither.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def cfg_key(cfg):
    """A configuration's sizes as a hashable key, for the families' caches
    of jitted functions (lists of sizes become tuples, notes are left out)."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
        if isinstance(v, (int, float, str))
        or (isinstance(v, list) and all(isinstance(x, int) for x in v))))


def cfg_of(key):
    return {k: list(v) if isinstance(v, tuple) else v for k, v in key}


def _adamw(opt):
    lr, b1, b2 = opt["lr"], opt["b1"], opt["b2"]
    eps, wd = opt["eps"], opt["weight_decay"]

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def update(w, g, state, t):
        m = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1 - b1) * g, state["m"], g)
        v = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        w = jax.tree_util.tree_map(
            lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * w), w, m, v)
        return w, {"m": m, "v": v}

    def init(w):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, w)
        return {"m": zeros, "v": jax.tree_util.tree_map(jnp.zeros_like, w)}

    return init, update


def _sgd_momentum(opt):
    lr, mom = opt["lr"], opt["momentum"]

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def update(w, g, state, t):
        tr = jax.tree_util.tree_map(lambda tr, g: g + mom * tr,
                                    state["trace"], g)
        w = jax.tree_util.tree_map(lambda w, tr: w - lr * tr, w, tr)
        return w, {"trace": tr}

    def init(w):
        return {"trace": jax.tree_util.tree_map(jnp.zeros_like, w)}

    return init, update


OPTIMIZERS = {"adamw": _adamw, "sgd_momentum": _sgd_momentum}


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


@jax.jit
def diff_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


def to_floats(tree):
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def _faulty_rows(batch, fault, chips):
    if fault is None:
        return batch
    rows = np.asarray(batch[0]).shape[0]
    keep = {"half_batch": rows // 2, "no_exchange": rows // chips}[fault]
    return tuple(np.asarray(a)[:keep] for a in batch)


def first_steps(ref, cfg, workload, seed_halves, batches, *,
                precision="float32", fault=None, chips=1):
    """Run the reference through ``len(batches)`` steps from the seed's
    weights. Returns each step's loss, the first gradient's norm by leaf and
    the norm of every leaf's change after the last step."""
    init, update = OPTIMIZERS[workload["optimizer"]["name"]](
        workload["optimizer"])
    w = ref.make_weights(cfg, seed_halves)
    state = init(w)
    losses, grad_norms = [], None
    kw = dict(workload.get("reference", {}))
    for t, batch in enumerate(batches, start=1):
        loss, g = ref.loss_and_grads(
            cfg, w, *_faulty_rows(batch, fault, chips), precision=precision,
            **kw)
        if grad_norms is None:
            grad_norms = to_floats(leaf_norms(g))
        w, state = update(w, g, state, float(t))
        del g
        losses.append(float(loss))
    # the update donates its weights, so the first ones are made anew
    w0 = ref.make_weights(cfg, seed_halves)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": to_floats(diff_norms(w, w0))}
