"""What the plain references share: seeded parameters, the contraction
with its optional lower-precision control, the losses, and the plain
sgd-with-momentum step in blocks of rows.  Imports nothing of the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed):
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_leaf(key, index, shape, init):
    """Leaf number *index* of a parameter table, float32 (traceable)."""
    if init[0] == "normal":
        return init[1] * jax.random.normal(
            jax.random.fold_in(key, index), shape, jnp.float32)
    if init[0] == "ones":
        return jnp.ones(shape, jnp.float32)
    if init[0] == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init[0] == "const":
        return jnp.full(shape, init[1], jnp.float32)
    raise ValueError("unknown init %r" % (init,))


def init_params(table, seed):
    """Every leaf of *table* (``name -> (shape, init)``) from *seed*, in
    one jitted call, float32, on the default device."""

    def make(key):
        return {n: make_leaf(key, i, shape, init)
                for i, (n, (shape, init)) in enumerate(table.items())}

    return jax.jit(make)(seed_key(seed))


def distance_from_init(table, seed, leaves):
    """``name -> |leaf - its seeded value|`` for *leaves* (``name ->
    array``): the seeded value is made again inside the
    program that takes the norm, so no second copy of the model is held."""
    names = list(leaves)
    index = {n: i for i, n in enumerate(table)}

    @jax.jit
    def norms(key, arrays):
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32)
                - make_leaf(key, index[n], *table[n]))))
            for n, a in zip(names, arrays)])

    vals = norms(seed_key(seed), [leaves[n] for n in names])
    return dict(zip(names, np.asarray(vals, np.float64).tolist()))


def _per_tensor(x, dtype, top):
    """*x* rounded to *dtype* with one scale for the whole tensor."""
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _rounder(forward, backward):
    """Identity-shaped: the value rounded by *forward* on the way in, the
    gradient rounded by *backward* on the way back."""
    @jax.custom_vjp
    def f(x):
        return forward(x)

    f.defvjp(lambda x: (forward(x), None), lambda _, g: (backward(g),))
    return f


def _same(x):
    return x


# the control's lower precision, the usual recipe for training in fp8:
# e4m3 operands, the output's gradient rounded to e5m2 before the two
# backward contractions, one scale a tensor, sums in float32
_fp8_operand = _rounder(
    lambda x: _per_tensor(x, jnp.float8_e4m3fn, 448.0), _same)
_fp8_gradient = _rounder(
    _same, lambda g: _per_tensor(g, jnp.float8_e5m2, 57344.0))


def contraction(fn, a, b, fp8=False):
    """``fn(a, b)`` in float32, or as the control computes it: operands
    and output gradient put through fp8."""
    if not fp8:
        return fn(a, b)
    return _fp8_gradient(fn(_fp8_operand(a), _fp8_operand(b)))


def dot(a, b, fp8=False):
    return contraction(jnp.matmul, a, b, fp8)


def layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def softmax_xent(logits, labels):
    """Per-position cross-entropy of integer *labels* (last axis classes)."""
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]


def leaf_norms(tree):
    """``name -> float`` L2 norm of every leaf, read back once."""
    names = sorted(tree)
    vals = _norms([tree[n] for n in names])
    return dict(zip(names, np.asarray(vals, np.float64).tolist()))


@jax.jit
def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in leaves])


@jax.jit
def _difference(a, b):
    return jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b))


def relative_difference(other, tree):
    """|other - tree| / |tree| over all the leaves together, *other* being
    host arrays under the same names: sent up one leaf at a time, so that
    no second copy sits on the device."""
    diff = norm = 0.0
    for n, b in tree.items():
        d, r = _difference(jnp.asarray(other[n]), b)
        diff += float(d)
        norm += float(r)
    return (diff / norm) ** 0.5


def follow_steps(loss_sum, params, batches, opt, distance,
                 rows_per_block=None, first_update=None,
                 keep_first_update=False):
    """The plain optimizer followed over *batches* (a list of ``(x, y)``
    numpy pairs) from *params*, which it consumes: returns the readings
    `correct` compares.  ``distance(params)`` gives each leaf's distance
    from where it started (`distance_from_init`).

    ``loss_sum(params, x, y)`` is the sum over rows of the per-row loss;
    where rows do not interact the gradient is accumulated over blocks of
    *rows_per_block* rows so that the step fits.  *opt* holds ``lr``,
    ``momentum`` and ``wd``: ``mom = momentum*mom - lr*(g + wd*w)``,
    ``w += mom``.  *first_update* is another side's first update (host
    arrays), to be measured against this one's while it is at hand; with
    *keep_first_update* this side's is handed back as host arrays.
    """
    lr, mu, wd = opt["lr"], opt.get("momentum", 0.0), opt.get("wd", 0.0)
    grad = jax.jit(jax.value_and_grad(loss_sum))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, g):
        return jax.tree_util.tree_map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply(w, mom, g, rows):
        def one(w, m, g):
            m = mu * m - lr * (g / rows + wd * w)
            return w + m, m
        pairs = {n: one(w[n], mom[n], g[n]) for n in w}
        return ({n: p[0] for n, p in pairs.items()},
                {n: p[1] for n, p in pairs.items()})

    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": [], "first_update_norms": None}
    for x, y in batches:
        rows = x.shape[0]
        blk = rows_per_block or rows
        total, acc = 0.0, None
        for lo in range(0, rows, blk):
            val, g = grad(params, jnp.asarray(x[lo:lo + blk]),
                          jnp.asarray(y[lo:lo + blk]))
            total += float(val)
            acc = g if acc is None else accumulate(acc, g)
        out["losses"].append(total / rows)
        params, mom = apply(params, mom, acc, float(rows))
        if out["first_update_norms"] is None:
            # |mom_1| = lr * |g + wd*w|: the first gradient as the
            # optimizer got it
            out["first_update_norms"] = leaf_norms(mom)
            if first_update is not None:
                out["first_update_difference"] = relative_difference(
                    first_update, mom)
            if keep_first_update:
                out["first_update"] = {n: np.asarray(v)
                                       for n, v in mom.items()}
    out["total_update_norms"] = distance(params)
    return out
