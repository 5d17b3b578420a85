"""Chase at the paper's 72x96x3 input: the agent pursues a scripted bot
that runs away, on a grid whose every cell is painted as a block of
pixels (9 x 12 cells of 8 x 8 pixels give exactly 72 x 96 x 3 frames).

The dynamics are the program's chase task (``repro.data.envs.make_chase``)
on a rectangular grid: five actions (up, down, left, right, stay), a
reward of 1 for a tag and -0.01 per step otherwise, and an episode that
ends after three tags or ``horizon`` steps, then resets. It stands in for
DMLab, which cannot be brought in here; it is pure JAX, so the actors'
unroll and the inference actors' env step run it on the device.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.data.envs import Env, TimeStep


def make(grid=(9, 12), cell_px: int = 8, horizon: int = 120) -> Env:
    rows, cols = int(grid[0]), int(grid[1])
    hw = (rows * cell_px, cols * cell_px, 3)
    hi = jnp.array([rows - 1, cols - 1])
    pix_r = jnp.arange(hw[0]) // cell_px
    pix_c = jnp.arange(hw[1]) // cell_px
    moves = jnp.array([[-1, 0], [1, 0], [0, -1], [0, 1], [0, 0]])

    class S(NamedTuple):
        agent: jax.Array
        bot: jax.Array
        t: jax.Array
        caught: jax.Array

    def block(pos):
        on = (pix_r[:, None] == pos[0]) & (pix_c[None, :] == pos[1])
        return on.astype(jnp.uint8) * jnp.uint8(255)

    def _obs(s: S, reward=0.0, done=False) -> TimeStep:
        token = ((s.agent[0] * cols + s.agent[1]) * rows * cols
                 + s.bot[0] * cols + s.bot[1])
        zero = jnp.zeros(hw[:2], jnp.uint8)
        img = jnp.stack([block(s.bot), block(s.agent), zero], axis=-1)
        return TimeStep(token.astype(jnp.int32), img, jnp.float32(reward),
                        jnp.asarray(done))

    def reset(key):
        k1, k2 = jax.random.split(key)
        return S(jax.random.randint(k1, (2,), 0, hi + 1),
                 jax.random.randint(k2, (2,), 0, hi + 1),
                 jnp.int32(0), jnp.int32(0))

    def step(s: S, action, key):
        agent = jnp.clip(s.agent + moves[action], 0, hi)
        delta = jnp.sign(s.bot - agent)
        delta = jnp.where(delta == 0,
                          jax.random.randint(key, (2,), -1, 2), delta)
        bot = jnp.clip(s.bot + delta, 0, hi)
        tagged = jnp.all(agent == bot)
        reward = jnp.where(tagged, 1.0, -0.01)
        caught = s.caught + tagged
        t = s.t + 1
        done = (caught >= 3) | (t >= horizon)
        nxt = S(agent, bot, t, caught)
        fresh = reset(jax.random.fold_in(key, 1))
        nxt = jax.tree.map(lambda a, b: jnp.where(done, a, b), fresh, nxt)
        return nxt, _obs(nxt, reward, done)

    return Env("chase72x96", 5, (rows * cols) ** 2, hw, reset, step,
               lambda s: _obs(s))
