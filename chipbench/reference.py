"""The plain reference: the IMPALA conv-LSTM agent, its V-trace loss and
one RMSProp step, written from the configuration file alone in
straightforward ``jax.numpy``.

It imports nothing of the program under test. It builds the weights
itself from the seed (``init_params``), in the tree layout the program
reads, and the harness hands that same tree to the program; the
reference rebuilds it from the seed again when it checks a run, so it
takes nothing the program made except the trajectories it trained on.

The agent (arXiv:1802.01561 Fig. 3, as the program builds it):

  torso    shallow: conv 8x8/4 x16, conv 4x4/2 x32, FC, ReLU after each;
           deep: three sections of [conv 3x3, max-pool 3x3/2, two residual
           blocks of (ReLU, conv 3x3, ReLU, conv 3x3)] with 16, 32, 32
           channels, then ReLU, FC, ReLU. Every conv and pool pads SAME.
  core     LSTM over [torso features, one-hot last action, last reward],
           state reset before a step whose ``done_in`` is set, forget
           gate biased by +1; then an FC + ReLU.
  heads    policy logits and a scalar value.

Departures from the paper, all the program's: the post-LSTM FC layer, the
+1 forget bias, SAME padding in the shallow torso, and the unclipped last
reward in the core's input.

The loss is V-trace (paper §4) summed over rows and time; a data-parallel
learner over ``shards`` devices takes the mean of the shards' sums, which
is the sum divided by ``shards``. Rows are independent, so gradients are
summed over blocks of rows: one compiled program per block shape, at
any batch.

Every matmul and conv runs in float32 at ``HIGHEST`` precision. With
``round_to`` set, the inputs of every matmul and conv (weights and
activations) are first rounded to that type, as a lower-precision
program's would be; the rest stays float32. On a TPU the program's
float32 matmuls at the default precision take one bfloat16 pass; the
control is the precision below that, ``float8_e4m3fn``.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DEEP_CHANNELS = (16, 32, 32)


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# weights


def _conv_shapes(kh, kw, cin, cout):
    return {"kernel": (kh, kw, cin, cout), "bias": (cout,)}


def _dense_shapes(d_in, d_out, bias=True):
    out = {"kernel": (d_in, d_out)}
    if bias:
        out["bias"] = (d_out,)
    return out


def _pooled(n: int, times: int) -> int:
    for _ in range(times):
        n = math.ceil(n / 2)
    return n


def param_shapes(cfg: Dict) -> Dict:
    """Every leaf's shape, keyed as the program keys its parameters."""
    h, w, c = cfg["frame"]
    a, width, fc = cfg["num_actions"], cfg["lstm_width"], cfg["fc_width"]
    flat = _pooled(h, 3) * _pooled(w, 3) * 32
    if cfg["torso"] == "shallow":
        torso = {"conv1": _conv_shapes(8, 8, c, 16),
                 "conv2": _conv_shapes(4, 4, 16, 32)}
    else:
        torso, cin = {}, c
        for s, ch in enumerate(DEEP_CHANNELS):
            sec = {"conv": _conv_shapes(3, 3, cin, ch)}
            for b in range(2):
                sec[f"res{b}a"] = _conv_shapes(3, 3, ch, ch)
                sec[f"res{b}b"] = _conv_shapes(3, 3, ch, ch)
            torso[f"section{s}"] = sec
            cin = ch
    torso["fc"] = _dense_shapes(flat, fc)
    return {
        "torso": torso,
        "lstm": {"wx": _dense_shapes(fc + a + 1, 4 * width),
                 "wh": _dense_shapes(width, 4 * width, bias=False)},
        "post_lstm": _dense_shapes(width, fc),
        "policy": _dense_shapes(fc, a),
        "value": _dense_shapes(fc, 1),
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def init_params(cfg: Dict, seed: int):
    """The run's weights, made on the default device in one jitted call:
    kernels normal with std 1/sqrt(fan-in), biases zero, all float32."""
    shapes = param_shapes(cfg)
    paths_shapes = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)[0]
    treedef = jax.tree.structure(shapes, is_leaf=_is_shape)

    def make(key):
        keys = jax.random.split(key, len(paths_shapes))
        leaves = []
        for (path, shape), k in zip(paths_shapes, keys):
            names = [p.key for p in path]
            if names[-1] == "bias":
                leaves.append(jnp.zeros(shape, jnp.float32))
                continue
            fan_in = int(np.prod(shape[:-1]))
            leaves.append(jax.random.normal(k, shape, jnp.float32)
                          / math.sqrt(fan_in))
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(make)(key_from_seed(seed))


# ---------------------------------------------------------------------------
# the agent


def _round(x, round_to):
    return x if round_to is None else x.astype(round_to).astype(x.dtype)


def _conv(p, x, stride, round_to=None):
    y = jax.lax.conv_general_dilated(
        _round(x, round_to), _round(p["kernel"], round_to),
        (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return y + p["bias"]


def _dense(p, x, round_to=None):
    y = jnp.dot(_round(x, round_to), _round(p["kernel"], round_to),
                precision=HIGHEST)
    return y + p["bias"] if "bias" in p else y


def _maxpool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")


def torso(cfg, p, images, round_to=None):
    """(N, H, W, C) uint8 frames -> (N, fc_width) features."""
    relu = jax.nn.relu
    x = images.astype(jnp.float32) / 255.0
    if cfg["torso"] == "shallow":
        x = relu(_conv(p["conv1"], x, 4, round_to))
        x = relu(_conv(p["conv2"], x, 2, round_to))
    else:
        for s in range(len(DEEP_CHANNELS)):
            sec = p[f"section{s}"]
            x = _maxpool(_conv(sec["conv"], x, 1, round_to))
            for b in range(2):
                y = _conv(sec[f"res{b}a"], relu(x), 1, round_to)
                x = x + _conv(sec[f"res{b}b"], relu(y), 1, round_to)
        x = relu(x)
    x = x.reshape(x.shape[0], -1)
    return relu(_dense(p["fc"], x, round_to))


def agent(cfg, params, rows: Dict, round_to=None):
    """Logits (B, T+1, A) and values (B, T+1) over a block of trajectory
    rows."""
    f32 = jnp.float32
    img = rows["obs_image"]
    b, t1 = img.shape[:2]
    a = cfg["num_actions"]
    feats = torso(cfg, params["torso"],
                  img.reshape((b * t1,) + img.shape[2:]),
                  round_to).reshape(b, t1, -1)
    core_in = jnp.concatenate(
        [feats, jax.nn.one_hot(rows["last_action"], a, dtype=f32),
         rows["last_reward"][..., None].astype(f32)], axis=-1)
    lp = params["lstm"]
    h0, c0 = (x.astype(f32) for x in rows["lstm_state"])

    def step(carry, inp):
        x, done = inp
        keep = (1.0 - done.astype(f32))[:, None]
        h, c = carry[0] * keep, carry[1] * keep
        gates = _dense(lp["wx"], x, round_to) + _dense(lp["wh"], h, round_to)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    _, ys = jax.lax.scan(step, (h0, c0),
                         (jnp.moveaxis(core_in, 1, 0),
                          jnp.moveaxis(rows["done_in"], 1, 0)))
    y = jax.nn.relu(_dense(params["post_lstm"], jnp.moveaxis(ys, 0, 1), round_to))
    logits = _dense(params["policy"], y, round_to)
    values = _dense(params["value"], y, round_to)[..., 0]
    return logits, values


# ---------------------------------------------------------------------------
# the loss


def vtrace_loss(learn: Dict, logits, values, rows: Dict):
    """The IMPALA loss summed over the block's rows and steps, and the
    target log-probabilities of the taken actions, (B, T)."""
    t = rows["actions"].shape[1]
    logp_all = jax.nn.log_softmax(logits[:, :t], axis=-1)
    tlp = jnp.take_along_axis(logp_all, rows["actions"][..., None],
                              axis=-1)[..., 0]
    rewards = rows["rewards"]
    if learn["reward_clip"] == "abs_one":
        rewards = jnp.clip(rewards, -1.0, 1.0)
    disc = rows["discounts"]
    v_t, v_tp1 = values[:, :t], values[:, 1:]
    rho = jnp.exp(tlp - rows["behaviour_logprob"])
    rho_c = jnp.minimum(learn["rho_bar"], rho)
    c = learn["lambda"] * jnp.minimum(learn["c_bar"], rho)
    deltas = rho_c * (rewards + disc * v_tp1 - v_t)

    def back(acc, inp):
        d, g, cc = inp
        acc = d + g * cc * acc
        return acc, acc

    _, accs = jax.lax.scan(
        back, jnp.zeros_like(v_t[:, 0]),
        (deltas.T, disc.T, c.T), reverse=True)
    vs = jax.lax.stop_gradient(v_t + accs.T)
    vs_tp1 = jnp.concatenate([vs[:, 1:], v_tp1[:, -1:]], axis=1)
    pg_adv = jax.lax.stop_gradient(rho_c * (rewards + disc * vs_tp1 - v_t))
    pg = -jnp.sum(pg_adv * tlp)
    baseline = 0.5 * jnp.sum(jnp.square(vs - v_t))
    neg_entropy = jnp.sum(jnp.exp(logp_all) * logp_all)
    total = (pg + learn["baseline_cost"] * baseline
             + learn["entropy_cost"] * neg_entropy)
    return total, tlp


# ---------------------------------------------------------------------------
# one step over a whole batch, in blocks of rows


ROW_KEYS = ("obs_image", "last_action", "last_reward", "done_in",
            "lstm_state", "actions", "rewards", "discounts",
            "behaviour_logprob")


@functools.lru_cache(maxsize=None)
def _block_fn(cfg_key: str, round_to):
    import json

    cfg = json.loads(cfg_key)

    def loss(params, rows):
        logits, values = agent(cfg, params, rows, round_to)
        return vtrace_loss(cfg["learning"], logits, values, rows)

    def fn(params, rows):
        (total, tlp), grads = jax.value_and_grad(loss, has_aux=True)(
            params, rows)
        return total, tlp, grads

    return jax.jit(fn)


def _block_rows(n: int) -> int:
    for b in (8, 4, 2, 1):
        if n % b == 0:
            return b
    return 1


def loss_and_grads(cfg: Dict, params, batch: Dict, round_to=None):
    """Summed loss, target log-probs (B, T) and summed gradients over a
    host batch, computed in blocks of rows on the default device."""
    import json

    fn = _block_fn(json.dumps(cfg, sort_keys=True), round_to)
    n = batch["actions"].shape[0]
    blk = _block_rows(n)
    total, tlps, grads = 0.0, [], None
    for lo in range(0, n, blk):
        rows = {k: jax.tree.map(lambda x: x[lo:lo + blk], batch[k])
                for k in ROW_KEYS}
        l, tlp, g = fn(params, rows)
        total += float(l)
        tlps.append(np.asarray(tlp))
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, np.concatenate(tlps, axis=0), grads


def optimizer_step(learn: Dict, params, ms, grads):
    """Clip by global norm, then TF-style RMSProp without momentum."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, learn["grad_clip_norm"] / jnp.maximum(
        norm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    decay, eps, lr = (learn["rmsprop_decay"], learn["rmsprop_eps"],
                      learn["learning_rate"])
    ms = jax.tree.map(lambda m, g: decay * m + (1 - decay) * g * g, ms,
                      grads)
    params = jax.tree.map(lambda p, g, m: p - lr * g / jnp.sqrt(m + eps),
                          params, grads, ms)
    return params, ms, grads


def run_steps(cfg: Dict, seed: int, batches: List[Dict], shards: int = 1,
              round_to=None):
    """The reference's own training from the seed's weights over the
    given batches, in order. Returns per-step losses (as the program
    reports them: the shards' mean of their sums), the first step's
    target log-probs (B, T) under the initial weights, the first step's
    clipped gradient, and the weights before and after."""
    learn = cfg["learning"]
    p0 = init_params(cfg, seed)
    params = p0
    ms = jax.tree.map(jnp.zeros_like, params)
    losses, first_tlp, first_grads = [], None, None
    for i, batch in enumerate(batches):
        total, tlp, grads = loss_and_grads(cfg, params, batch, round_to)
        grads = jax.tree.map(lambda g: g / shards, grads)
        params, ms, clipped = optimizer_step(learn, params, ms, grads)
        losses.append(total / shards)
        if i == 0:
            first_tlp, first_grads = tlp, clipped
    return {"losses": losses, "first_tlp": first_tlp,
            "first_grads": jax.device_get(first_grads),
            "params0": jax.device_get(p0),
            "params": jax.device_get(params)}


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(x, np.float64))) for p, x in flat}


def tree_delta(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)
