"""Plain reference of W-walker RW-SGD with periodic model averaging.

Each walker w holds a linear model x_w.  At step t it is at node v =
nodes[w, t] and takes one importance-weighted SGD step on that node's
squared loss f_v(x) = (y_v - A_v . x)^2 (arXiv:2407.20611, Eq. 12 and
Appendix D):

    x_w <- x_w - gamma * (mean(L) / L_v) * (-2 (y_v - A_v . x_w) A_v)

and every ``avg_every``-th step all models are replaced by their mean.
After each step the losses are read over all n rows: each walker's
mean_v (y_v - A_v . x_w)^2 and that of the averaged model.

The walk stream is taken as given (the nodes the program's walkers
visited), so the updates and losses are judged on their own; the walk
itself is judged by ``reference.walk``.  Arithmetic is jax.numpy in the
dtype asked for: float32 with every product at ``precision=HIGHEST`` is
the reference, bfloat16 is the lower-precision control.  The per-walker
losses' product A x_w takes its inputs in ``loss_inputs``: the precision
the configuration states for it (on TPU one bfloat16 pass: the inputs
rounded to bfloat16, products and sums in float32).  Nothing of the
program is imported.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _losses(x, a, y, block, inputs=None):
    """Per-walker mean squared residual over all rows, in walker blocks;
    the product's inputs are first rounded to ``inputs`` where given."""
    w, dim = x.shape
    if inputs is not None:
        a = a.astype(inputs).astype(a.dtype)
        x = x.astype(inputs).astype(x.dtype)
    x = jnp.pad(x, ((0, -w % block), (0, 0)))

    def one_block(xb):
        pred = jnp.matmul(a, xb.T, precision=HIGHEST)  # (n, block)
        resid = y[:, None] - pred
        return jnp.mean(resid * resid, axis=0, dtype=jnp.float32)

    return lax.map(one_block, x.reshape(-1, block, dim)).reshape(-1)[:w]


@functools.partial(jax.jit, static_argnames=("avg_every", "dtype", "block",
                                             "loss_inputs"))
def reference_call(x0, nodes, features, targets, weights, gamma, *, avg_every,
                   dtype, block, loss_inputs=None):
    """One call of ``nodes.shape[1]`` steps from models ``x0`` (W, dim).

    Returns (x after the call, per-walker losses (W, T + 1), averaged-model
    losses (T + 1,)); column 0 is the loss of ``x0``.
    """
    dt = jnp.dtype(dtype)
    a, y, wts = features.astype(dt), targets.astype(dt), weights.astype(dt)
    g = jnp.asarray(gamma, dt)
    w = x0.shape[0]
    block = min(block, w)

    def losses(x):
        per = _losses(x, a, y, block, loss_inputs)
        avg = _losses(jnp.mean(x, axis=0, keepdims=True, dtype=jnp.float32).astype(dt),
                      a, y, 1)[0]
        return per.astype(jnp.float32), avg.astype(jnp.float32)

    def step(carry, v):
        x, t = carry
        av, yv = a[v], y[v]
        resid = yv - jnp.sum(av * x, axis=1)
        x = x - (g * wts[v])[:, None] * (-2 * resid[:, None] * av)
        x = x.astype(dt)
        mean = jnp.mean(x, axis=0, keepdims=True, dtype=jnp.float32).astype(dt)
        x = jnp.where((t + 1) % avg_every == 0, jnp.broadcast_to(mean, x.shape), x)
        return (x, t + 1), losses(x)

    x0 = x0.astype(dt)
    (x, _), (per, avg) = lax.scan(step, (x0, 0), nodes.T)
    per0, avg0 = losses(x0)
    return (
        x.astype(jnp.float32),
        jnp.concatenate([per0[None], per]).T,
        jnp.concatenate([avg0[None], avg]),
    )
