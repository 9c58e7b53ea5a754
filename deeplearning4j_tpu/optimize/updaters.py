"""Updater zoo — per-param-type learning rules + schedules + grad clipping.

Reference: ``nn/updater/BaseUpdater.java:72-168`` (preApply gradient
normalization, lr/momentum decay policies), ``UpdaterCreator.java:31-38``
(SGD/Adam/AdaGrad/AdaDelta/Nesterovs/RMSProp/NoOp), ``MultiLayerUpdater``
fan-out per layer.  Re-derived as pure functions over parameter pytrees:
``init_state(cfg, params)`` and ``update(cfg, grads, state, iteration,
lr_overrides)`` -> (updates-to-subtract, new state).  Everything is jit-safe
(schedules compile to ``jnp.select`` over static breakpoints), so the whole
optimizer lives inside the one XLA program and shards with the params.

This module is self-contained rather than wrapping optax so that reference
semantics (per-layer lr overrides, per-layer gradient normalization, momentum
schedules) are exact; optax interop is provided via ``as_optax``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import UpdaterConfig


# ---------------------------------------------------------------------------
# learning-rate / momentum schedules (reference LearningRatePolicy + decay maps)
# ---------------------------------------------------------------------------

def schedule_value(base: float, policy: str, cfg: UpdaterConfig, iteration,
                   schedule: Optional[Dict[int, float]] = None):
    it = jnp.asarray(iteration, jnp.float32)
    if policy == "none":
        return jnp.asarray(base, jnp.float32)
    if policy == "exponential":
        return base * jnp.power(cfg.lr_policy_decay_rate, it)
    if policy == "inverse":
        return base / jnp.power(1.0 + cfg.lr_policy_decay_rate * it, cfg.lr_policy_power)
    if policy == "step":
        return base * jnp.power(cfg.lr_policy_decay_rate, jnp.floor(it / cfg.lr_policy_steps))
    if policy == "poly":
        frac = jnp.clip(it / jnp.maximum(cfg.lr_policy_steps, 1.0), 0.0, 1.0)
        return base * jnp.power(1.0 - frac, cfg.lr_policy_power)
    if policy == "sigmoid":
        return base / (1.0 + jnp.exp(-cfg.lr_policy_decay_rate * (it - cfg.lr_policy_steps)))
    if policy == "warmup_cosine":
        # linear warmup to base over lr_warmup_steps, then cosine decay to
        # base * lr_min_fraction at lr_policy_steps (total steps) — the
        # standard transformer-training schedule (no reference analog:
        # LearningRatePolicy predates it)
        warm = jnp.maximum(cfg.lr_policy_warmup_steps, 1.0)
        total = jnp.maximum(cfg.lr_policy_steps, warm + 1.0)
        warm_frac = jnp.minimum(it / warm, 1.0)
        prog = jnp.clip((it - warm) / (total - warm), 0.0, 1.0)
        floor = cfg.lr_policy_min_fraction
        cos = floor + (1.0 - floor) * 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
        return base * warm_frac * cos
    if policy == "schedule":
        # piecewise-constant: value switches at each breakpoint iteration
        if not schedule:
            return jnp.asarray(base, jnp.float32)
        val = jnp.asarray(base, jnp.float32)
        for step_i in sorted(schedule):
            val = jnp.where(it >= step_i, schedule[step_i], val)
        return val
    raise ValueError(f"Unknown lr policy '{policy}'")


def current_lr(cfg: UpdaterConfig, iteration, override: Optional[float] = None):
    base = override if override is not None else cfg.learning_rate
    return schedule_value(base, cfg.lr_policy, cfg, iteration, cfg.lr_schedule)


def current_momentum(cfg: UpdaterConfig, iteration):
    if cfg.momentum_schedule:
        return schedule_value(cfg.momentum, "schedule", cfg, iteration, cfg.momentum_schedule)
    return jnp.asarray(cfg.momentum, jnp.float32)


# ---------------------------------------------------------------------------
# gradient normalization (reference BaseUpdater.preApply / GradientNormalization)
# ---------------------------------------------------------------------------

def _global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l * l) for l in leaves))


def normalize_gradients(cfg: UpdaterConfig, layer_grads: Dict[str, jax.Array]):
    """Apply the configured normalization to ONE layer's gradient dict."""
    kind = cfg.gradient_normalization
    t = cfg.gradient_normalization_threshold
    if kind == "none":
        return layer_grads
    if kind == "renormalize_l2_per_layer":
        norm = _global_norm(layer_grads)
        return jax.tree_util.tree_map(lambda g: g / (norm + 1e-12), layer_grads)
    if kind == "renormalize_l2_per_param_type":
        return {k: g / (jnp.linalg.norm(g.reshape(-1)) + 1e-12) for k, g in layer_grads.items()}
    if kind == "clip_element_wise_absolute_value":
        return jax.tree_util.tree_map(lambda g: jnp.clip(g, -t, t), layer_grads)
    if kind == "clip_l2_per_layer":
        norm = _global_norm(layer_grads)
        scale = jnp.where(norm > t, t / (norm + 1e-12), 1.0)
        return jax.tree_util.tree_map(lambda g: g * scale, layer_grads)
    if kind == "clip_l2_per_param_type":
        out = {}
        for k, g in layer_grads.items():
            norm = jnp.linalg.norm(g.reshape(-1))
            out[k] = g * jnp.where(norm > t, t / (norm + 1e-12), 1.0)
        return out
    raise ValueError(f"Unknown gradient normalization '{kind}'")


# ---------------------------------------------------------------------------
# per-updater state + step rules
# ---------------------------------------------------------------------------

def init_state(cfg: UpdaterConfig, params):
    """Per-leaf optimizer state pytree (reference updater stateViewArray)."""
    name = cfg.name
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    if name in ("sgd", "none", "noop"):
        return {}
    if name == "nesterovs":
        return {"v": zeros()}
    if name == "adagrad":
        return {"h": zeros()}
    if name == "rmsprop":
        return {"ms": zeros()}
    if name == "adadelta":
        return {"msg": zeros(), "msdx": zeros()}
    if name in ("adam", "adamw"):
        return {"m": zeros(), "v": zeros()}
    raise ValueError(f"Unknown updater '{cfg.name}'")


def _flat(d, prefix=()):
    """Flatten a layer's (possibly nested — composite layers) param dict
    to {tuple-path: leaf}."""
    out = {}
    for k, v in d.items():
        if isinstance(v, dict):
            out.update(_flat(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = v
    return out


def _unflat(flat):
    out = {}
    for path, v in flat.items():
        cur = out
        for k in path[:-1]:
            cur = cur.setdefault(k, {})
        cur[path[-1]] = v
    return out


def normalize_tree(cfg: UpdaterConfig, grads):
    """Apply the configured per-layer gradient normalization to a whole
    gradient tree — the same flatten/normalize walk ``update`` performs
    internally, exposed for callers that must normalize on the FULL
    per-layer gradients BEFORE scattering them into shards
    (``parallel/zero.py``: shard-local norms would be wrong) and then
    run ``update`` with normalization disabled."""
    if cfg.gradient_normalization == "none":
        return grads
    return {lname: _unflat(normalize_gradients(cfg, _flat(lgrads)))
            for lname, lgrads in grads.items()}


# every trainer's step reaches the optimizer through these two functions, so
# the ``updater`` scope on its device operations is entered here, once
@jax.named_scope("updater")
def update(
    cfg: UpdaterConfig,
    grads,
    state,
    iteration,
    lr_overrides: Optional[Dict[str, float]] = None,
    params=None,
):
    """Compute updates (to SUBTRACT from params) and new updater state.

    ``grads``/``params`` pytrees are {layer_name: {param_name: arr}} — the
    inner dict may nest further (composite layers, e.g. ResidualBlock), so
    each layer's subtree is walked by tuple path; gradient normalization is
    per-layer (the reference normalizes within each layer's gradient view);
    lr_overrides maps layer_name -> lr.
    """
    lr_overrides = lr_overrides or {}
    name = cfg.name
    if name == "adamw" and params is None:
        raise ValueError(
            "adamw applies decoupled weight decay to the parameters; pass "
            "params= to updaters.update() (all facade train steps do)")
    mu = current_momentum(cfg, iteration)
    it = jnp.asarray(iteration, jnp.float32)

    new_state = {k: {} for k in state}
    updates = {}
    for lname, lgrads in grads.items():
        lgrads = _flat(lgrads)
        lparams_flat = _flat(params[lname]) if params is not None else {}
        lstate_flat = {k: _flat(state[k].get(lname, {})) for k in state}
        lgrads = normalize_gradients(cfg, lgrads)
        lr = current_lr(cfg, it, lr_overrides.get(lname))
        lup = {}
        lns = {k: {} for k in state}
        for pname, g in lgrads.items():
            if name in ("sgd",):
                u = lr * g
            elif name in ("none", "noop"):
                u = g
            elif name == "nesterovs":
                v_prev = lstate_flat["v"][pname]
                v = mu * v_prev - lr * g
                # reference Nesterov: update = -(mu * v - lr*g) applied as
                # params += mu*v_new - lr*g  =>  subtract -(mu*v - lr*g)
                u = -(mu * v - lr * g)
                lns["v"][pname] = v
            elif name == "adagrad":
                h = lstate_flat["h"][pname] + g * g
                u = lr * g / (jnp.sqrt(h) + cfg.epsilon)
                lns["h"][pname] = h
            elif name == "rmsprop":
                ms = cfg.rmsprop_decay * lstate_flat["ms"][pname] + (1 - cfg.rmsprop_decay) * g * g
                u = lr * g / jnp.sqrt(ms + cfg.epsilon)
                lns["ms"][pname] = ms
            elif name == "adadelta":
                msg = cfg.rho * lstate_flat["msg"][pname] + (1 - cfg.rho) * g * g
                msdx_prev = lstate_flat["msdx"][pname]
                dx = jnp.sqrt((msdx_prev + cfg.epsilon) / (msg + cfg.epsilon)) * g
                msdx = cfg.rho * msdx_prev + (1 - cfg.rho) * dx * dx
                u = dx  # adadelta has no lr
                lns["msg"][pname] = msg
                lns["msdx"][pname] = msdx
            elif name in ("adam", "adamw"):
                m = cfg.adam_beta1 * lstate_flat["m"][pname] + (1 - cfg.adam_beta1) * g
                v = cfg.adam_beta2 * lstate_flat["v"][pname] + (1 - cfg.adam_beta2) * g * g
                t = it + 1.0
                mhat = m / (1 - jnp.power(cfg.adam_beta1, t))
                vhat = v / (1 - jnp.power(cfg.adam_beta2, t))
                u = lr * mhat / (jnp.sqrt(vhat) + cfg.epsilon)
                if name == "adamw" and cfg.weight_decay:
                    # DECOUPLED decay (AdamW): acts on the param directly,
                    # not through the adaptive denominator
                    u = u + lr * cfg.weight_decay * lparams_flat[pname]
                lns["m"][pname] = m
                lns["v"][pname] = v
            else:
                raise ValueError(f"Unknown updater '{name}'")
            lup[pname] = u
        updates[lname] = _unflat(lup)
        for k, flat in lns.items():
            if flat:
                new_state[k][lname] = _unflat(flat)
    return updates, new_state


@jax.named_scope("updater")
def apply_updates(params, updates):
    return jax.tree_util.tree_map(lambda p, u: p - u, params, updates)


def as_optax(cfg: UpdaterConfig):
    """Optional optax interop for users who want the wider optax ecosystem."""
    import optax

    name = cfg.name
    lr = cfg.learning_rate
    if name == "sgd":
        return optax.sgd(lr)
    if name == "nesterovs":
        return optax.sgd(lr, momentum=cfg.momentum, nesterov=True)
    if name == "adam":
        return optax.adam(lr, b1=cfg.adam_beta1, b2=cfg.adam_beta2, eps=cfg.epsilon)
    if name == "adamw":
        return optax.adamw(lr, b1=cfg.adam_beta1, b2=cfg.adam_beta2,
                           eps=cfg.epsilon, weight_decay=cfg.weight_decay)
    if name == "adagrad":
        return optax.adagrad(lr, eps=cfg.epsilon)
    if name == "adadelta":
        return optax.adadelta(rho=cfg.rho, eps=cfg.epsilon)
    if name == "rmsprop":
        return optax.rmsprop(lr, decay=cfg.rmsprop_decay, eps=cfg.epsilon)
    raise ValueError(f"No optax equivalent for '{name}'")
