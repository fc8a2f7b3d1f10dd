"""Seeded text-like binary problem in the shape of LIBSVM ``rcv1.binary``.

Built on the device in one jitted call, elementwise: every value is a hash
of its feature's and its sample's index, so X needs no scatter and no
random stream of its size. Feature frequencies are Zipf-like (a few common
terms, a long tail): ``nnz`` term draws fall on feature r with probability
proportional to ``(r + zipf_offset)^-zipf_exponent`` and on a uniform
sample, and a position is nonzero when at least one draw lands on it,
independently per position. Values are exponential(1) + 0.1 and each sample
is scaled to unit norm, as in tf-idf text data. Labels are the sign of a
planted direction plus Gaussian noise, split at the median: +1 and -1 in
turn on every ``support_step``-th feature by frequency rank from rank
``support_first``.

One problem for every seed: the data are fixed by ``data_seed``, and the
run's seed only permutes the features and the samples. So every seed asks
for the same work, in another order.

X has shape (features, samples) in float32, the layout the solver takes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

#: streams of the hash: the position's value, two uniforms of label noise
_X, _NOISE_A, _NOISE_B = 1, 2, 3


def _mix(x):
    """A 32-bit integer hash (lowbias32); ``x`` is uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _uniform(salt, a, b):
    """A uniform on [0, 1) for each pair of indices ``(a, b)``."""
    h = _mix(_mix(a.astype(jnp.uint32) + salt) + b.astype(jnp.uint32))
    return (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


@partial(jax.jit, static_argnames=("m", "n", "nnz", "zipf_exponent",
                                   "zipf_offset", "support", "support_first",
                                   "support_step", "label_noise",
                                   "data_seed"))
def _build(key, *, m, n, nnz, zipf_exponent, zipf_offset, support,
           support_first, support_step, label_noise, data_seed):
    k_f, k_s = jax.random.split(key)
    # row i of X is feature perm_f[i] of the fixed problem, column j its
    # sample perm_s[j]
    perm_f = jax.random.permutation(k_f, m)
    perm_s = jax.random.permutation(k_s, n)

    def salt(stream):
        return _mix(jnp.uint32((data_seed * 4 + stream) % 2 ** 32))

    p = 1.0 / (jnp.arange(m, dtype=jnp.float32) + zipf_offset) ** zipf_exponent
    # chance that one of the nnz draws lands on a position of feature r
    q = (-jnp.expm1(-(nnz / n) * p / jnp.sum(p)))[perm_f][:, None]
    u = _uniform(salt(_X), perm_f[:, None], perm_s[None, :])
    # below q the position is nonzero, and u / q is uniform on [0, 1)
    X = jnp.where(u < q, 0.1 - jnp.log1p(-u / q), 0.0)
    norms = jnp.sqrt(jnp.sum(X * X, axis=0))
    X = X / jnp.maximum(norms, 1e-12)[None, :]

    ranks = support_first + support_step * jnp.arange(support)
    w_true = jnp.zeros((m,), jnp.float32).at[ranks].set(
        jnp.where(jnp.arange(support) % 2 == 0, 1.0, -1.0))[perm_f]
    scores = jnp.matmul(w_true, X, precision=jax.lax.Precision.HIGHEST)
    # Box-Muller, from two uniforms of the sample's own index
    zero = jnp.zeros_like(perm_s)
    ua = _uniform(salt(_NOISE_A), perm_s, zero)
    ub = _uniform(salt(_NOISE_B), perm_s, zero)
    noise = jnp.sqrt(-2.0 * jnp.log1p(-ua)) * jnp.cos(2.0 * jnp.pi * ub)
    scores = scores + label_noise * jnp.std(scores) * noise
    y = jnp.where(scores >= jnp.median(scores), 1.0, -1.0).astype(jnp.float32)
    return X, y


def generate(key, shape, params):
    """``(X, y)`` on the default device. ``shape`` holds ``features``,
    ``samples`` and ``density``; ``params`` the generator's own settings."""
    m, n = shape["features"], shape["samples"]
    first, step = int(params["support_first"]), int(params["support_step"])
    # a cut-down shape keeps the planted features inside its rows
    step = max(1, min(step, (m - first) // int(params["support"])))
    return _build(key, m=m, n=n, nnz=int(round(shape["density"] * m * n)),
                  zipf_exponent=float(params["zipf_exponent"]),
                  zipf_offset=float(params["zipf_offset"]),
                  support=int(params["support"]),
                  support_first=first, support_step=step,
                  label_noise=float(params["label_noise"]),
                  data_seed=int(params["data_seed"]))
