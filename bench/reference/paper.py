"""Plain reference of Algorithm 1 on the paper runtime's problems.

Imports nothing of the program.  It states the mathematics once, in
straightforward ``jax.numpy``: the LIBSVM-twin data from a seed, each
worker's closed-form gradient and Hessian, the cubic sub-problem solved by
the paper's Algorithm 2, the uplink (top-k and the Byzantine injection),
the norm-trimmed mean at the center, the downlink, and the bits each
round puts on the wire.  At ``float32`` every matrix product runs at
``highest`` precision; the control runs the same code in ``bfloat16``.

The random draws follow Algorithm 1's key schedule as the runtime spends
it: each round splits the solve's key, and the round key splits into the
label, update, compression, gradient and downlink keys, in that order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# ------------------------------------------------------------------ data
# The LIBSVM twins: a linear separator with label noise (logistic), or a
# linear model with heavy-tailed outliers (robust regression).  This is
# the benchmark's own copy of the generator; it runs at the platform's
# default matmul precision, as the program's does, so both make the same
# rows from the same seed.


def _classification(key, n, d, label_noise=0.05, margin=1.0):
    kx, kw, kn = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n, d))
    w_star = margin * jax.random.normal(kw, (d,)) / jnp.sqrt(d)
    p = jax.nn.sigmoid(X @ w_star / 0.5)
    y = (jax.random.uniform(kn, (n,)) < p).astype(jnp.float32)
    flip = jax.random.uniform(jax.random.fold_in(kn, 1), (n,)) < label_noise
    return X, jnp.where(flip, 1.0 - y, y)


def _regression(key, n, d, noise=0.1, outlier_frac=0.1, outlier_scale=10.0):
    kx, kw, kn, ko, km = jax.random.split(key, 5)
    X = jax.random.normal(kx, (n, d))
    w_star = jax.random.normal(kw, (d,)) / jnp.sqrt(d)
    y = X @ w_star + noise * jax.random.normal(kn, (n,))
    out = jax.random.uniform(km, (n,)) < outlier_frac
    return X, jnp.where(out, y + outlier_scale * jax.random.normal(ko, (n,)), y)


def make_data(data: dict, seed: int):
    """Worker shards ``(m, n, d)``, ``(m, n)`` of the training split."""
    key = jax.random.split(jax.random.PRNGKey(seed))[0]   # (train, test)
    make = _classification if data["loss"] == "logistic" else _regression
    X, y = make(key, data["n_train"], data["dim"])
    m = data["m_workers"]
    n = data["n_train"] // m
    return (X[: m * n].reshape(m, n, -1), y[: m * n].reshape(m, n))


# ----------------------------------------------------------------- wire
def index_bits(d: int) -> int:
    return max(1, (d - 1).bit_length())


def bits_per_round(m: int, d: int, topk: int | None) -> int:
    """m uplink payloads (full f32, or k values and k indices) and one
    f32 broadcast of the step."""
    up = 32 * d if topk is None else topk * (32 + index_bits(d))
    return m * up + 32 * d


# ----------------------------------------------------------------- math
class Reference:
    """Algorithm 1 over a fixed data set; ``dtype`` float32 or bfloat16."""

    def __init__(self, data: dict, solver: dict, rule: dict, X, y,
                 dtype=jnp.float32):
        self.loss = data["loss"]
        self.m, self.n, self.d = X.shape
        self.solver = solver
        self.rule = rule
        self.dtype = dtype
        self.precision = "highest" if dtype == jnp.float32 else None
        self.X = X.astype(dtype)
        self.y = y.astype(dtype)
        self.n_byz = int(rule["alpha"] * self.m)
        # the data goes in as arguments, not as constants of the programs:
        # they stay small enough for the persistent cache
        self.round = jax.jit(self._round)
        self.pooled = jax.jit(self._pooled)

    def _mm(self, a, b):
        return jnp.matmul(a, b, precision=self.precision)

    def _grad(self, w, X, y):
        n = X.shape[0]
        z = self._mm(X, w)
        if self.loss == "logistic":
            yy = 2 * y - 1
            return self._mm(X.T, -yy * jax.nn.sigmoid(-yy * z)) / n + w / n
        r = y - z
        return -self._mm(X.T, r / (1 + r * r / 2)) / n

    def _hess(self, w, X, y):
        n = X.shape[0]
        z = self._mm(X, w)
        if self.loss == "logistic":
            sz = jax.nn.sigmoid(z)
            eye = jnp.eye(self.d, dtype=w.dtype)
            return self._mm(X.T, X * (sz * (1 - sz))[:, None]) / n + eye / n
        r = y - z
        q = 1 + r * r / 2
        return self._mm(X.T, X * ((1 - r * r / 2) / (q * q))[:, None]) / n

    def _loss(self, w, X, y):
        z = self._mm(X, w)
        if self.loss == "logistic":
            yy = 2 * y - 1
            reg = 0.5 / X.shape[0] * self._mm(w, w)
            return jnp.mean(jnp.log1p(jnp.exp(-yy * z))) + reg
        r = y - z
        return jnp.mean(jnp.log(r * r / 2 + 1))

    def _pooled(self, w, X, y):
        """Gradient norm and loss on the pooled, uncorrupted shards."""
        X, y = X.reshape(-1, self.d), y.reshape(-1)
        g = self._grad(w, X, y)
        return jnp.sqrt(jnp.sum(g * g)), self._loss(w, X, y)

    def _cubic(self, g, H):
        """Algorithm 2: gradient descent on the cubic model until its
        gradient G falls under the tolerance (or the iteration cap)."""
        M, gam = self.solver["M"], self.solver["gamma"]
        tol, cap = self.solver["solver_tol"], self.solver["solver_iters"]
        lr = 1.0 / (gam * (jnp.sqrt(jnp.sum(H * H)) + M * gam) + 1e-8)

        def cond(c):
            it, _, G = c
            return (jnp.sqrt(jnp.sum(G * G)) > tol) & (it < cap)

        def body(c):
            it, s, G = c
            s = s - lr * G
            G = g + gam * self._mm(H, s) + 0.5 * M * gam ** 2 * jnp.sqrt(jnp.sum(s * s)) * s
            return it + 1, s, G

        return jax.lax.while_loop(cond, body, (0, jnp.zeros_like(g), g))[1]

    def _round(self, w, key, X, y):
        rule = self.rule
        k_label, k_update, _k_comp, _k_grad, _k_down = jax.random.split(key, 5)
        byz = (jnp.arange(self.m) < self.n_byz)[:, None]
        if rule["attack"] == "flipped_label":
            y = jnp.where(byz, 1 - y, y)
        elif rule["attack"] == "random_label":
            y = jnp.where(byz, jax.random.randint(k_label, y.shape, 0, 2).astype(y.dtype), y)

        g = jax.vmap(self._grad, in_axes=(None, 0, 0))(w, X, y)
        H = jax.vmap(self._hess, in_axes=(None, 0, 0))(w, X, y)
        s = jax.vmap(self._cubic)(g, H)

        # uplink: top-k keeps the k largest magnitudes, lowest index first
        # among equals; the Byzantine workers then replace what they send
        if rule.get("topk"):
            order = jnp.argsort(-jnp.abs(s), axis=1, stable=True)
            ranks = jnp.argsort(order, axis=1, stable=True)
            s = jnp.where(ranks < rule["topk"], s, 0)
        if rule["attack"] == "gaussian":
            s = jnp.where(byz, s + rule["sigma"] * jax.random.normal(k_update, s.shape, s.dtype), s)
        elif rule["attack"] == "negative":
            s = jnp.where(byz, -rule["c"] * s, s)
        elif rule["attack"] == "saddle":
            v = jax.random.normal(k_update, (self.d,), s.dtype)
            v = v / (jnp.sqrt(jnp.sum(v * v)) + 1e-12)
            s = jnp.where(byz, rule["scale"] * v, s)

        # center: keep the (1 - beta) m smallest norms, average them
        n_keep = max(1, int(round((1 - rule["beta"]) * self.m)))
        norms = jnp.sqrt(jnp.sum(s.astype(jnp.float32) ** 2, axis=1))
        rank = jnp.argsort(jnp.argsort(norms, stable=True), stable=True)
        keep = (rank < n_keep).astype(s.dtype)
        agg = jnp.sum(keep[:, None] * s, axis=0) / n_keep
        # downlink: the full-precision broadcast of eta * agg
        return w + self.solver["eta"] * agg

    def solve(self, key, rounds: int | None = None, eps: float | None = None,
              cap: int | None = None) -> dict:
        """Rounds from w = 0 with the solve's key: exactly ``rounds`` of
        them, or until the pooled gradient norm is at most ``eps`` (at
        most ``cap``).  Returns the iterate and the per-round pooled
        gradient norms and losses."""
        w = jnp.zeros((self.d,), self.dtype)
        gns, losses = [], []
        n = rounds if rounds is not None else cap
        for _ in range(n):
            key, sub = jax.random.split(key)
            w = self.round(w, sub, self.X, self.y)
            gn, loss = self.pooled(w, self.X, self.y)
            gns.append(float(gn))
            losses.append(float(loss))
            if rounds is None and gns[-1] <= eps:
                break
        return {"w": w.astype(jnp.float32), "grad_norm": gns, "loss": losses,
                "rounds": len(gns)}

