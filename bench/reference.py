"""Plain reference of the emulated rounds: synchronous decentralized SGD.

It imports nothing of the program.  From the seed and the harness's
inputs (dataset, partition) it redraws what the program draws itself, by
the rules the program documents:

* each node's batch of a round: ``batch_size`` indices drawn uniformly,
  with replacement, from the node's partition by one numpy PCG64 stream
  per round, seeded ``(seed * 1_000_003 + round) * 1_000_003 + 99_991``;
* the graph: the d-regular circulant (offsets 1 .. d//2, and N/2 for odd
  d) for ``topology="regular"``, or for ``"dynamic"`` a fresh random
  d-regular graph per round from the configuration-model sampler seeded
  ``seed * 100_003 + round``;
* the initial weights: the model reference's ``init`` on
  ``split(key(seed), N)``.

One round is: every node takes ``local_steps`` SGD steps on its batch;
then the nodes exchange.  ``full`` (and ``secure``, whose pairwise masks
cancel in each receiver's sum) is the Metropolis-Hastings average
x_i <- w_ii x_i + sum_j w_ij x_j.  ``topk`` lets every node select the k
coordinates with the largest |x - last shared| (exact top-k) and mix only
those: x_i[c] += sum_j w_ij m_j[c] (x_j[c] - x_i[c]).

``dtype`` is float32 (matmuls at HIGHEST) for the reference, or
bfloat16 for its control.  ``fault`` plants one of the faults the check
must catch: ``"half_batch"`` (the loss over half of each batch) or
``"no_exchange"`` (the round keeps each node's own model).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


# -- what the program draws itself, redrawn by its documented rules --------


def padded_parts(parts: List[np.ndarray]):
    lens = np.array([len(p) for p in parts], np.int64)
    pad = np.zeros((len(parts), int(lens.max())), np.int64)
    for i, p in enumerate(parts):
        pad[i, :len(p)] = p
        pad[i, len(p):] = p[0]
    return lens, pad


def batch_indices(seed: int, rnd: int, steps: int, batch: int, lens, pad) -> np.ndarray:
    """(steps, N, B) sample indices of round ``rnd``."""
    rng = np.random.default_rng((seed * 1_000_003 + rnd) * 1_000_003 + 99_991)
    n = len(lens)
    u = rng.random((steps, n, batch))
    loc = (u * lens[None, :, None]).astype(np.int64)
    return pad[np.arange(n)[None, :, None], loc].astype(np.int32)


def circulant_neighbors(n: int, d: int) -> np.ndarray:
    offs = list(range(1, d // 2 + 1)) + ([n // 2] if d % 2 else [])
    idx = np.arange(n)[:, None]
    cols = []
    for o in offs:
        cols.append((idx + o) % n)
        if (2 * o) % n:
            cols.append((idx - o) % n)
    nbr = np.concatenate(cols, axis=1)
    nbr.sort(axis=1)
    return nbr.astype(np.int32)


def random_regular_neighbors(n: int, d: int, seed: int) -> np.ndarray:
    """Configuration model: pair all N*d stubs at random, then re-pair the
    stubs of self-loops and repeated edges together with a batch of random
    good edges until the graph is simple."""
    rng = np.random.default_rng(seed)
    stubs = np.repeat(np.arange(n, dtype=np.int64), d)
    rng.shuffle(stubs)
    e = stubs.reshape(-1, 2)
    for _ in range(500):
        a, b = e.min(1), e.max(1)
        key = a * n + b
        order = np.argsort(key, kind="stable")
        sk = key[order]
        dup = np.zeros(key.shape, bool)
        dup[1:] = sk[1:] == sk[:-1]
        bad = a == b
        bad[order] |= dup
        n_bad = int(bad.sum())
        if n_bad == 0:
            src, dst = np.concatenate([a, b]), np.concatenate([b, a])
            return dst[np.argsort(src, kind="stable")].reshape(n, d).astype(np.int32)
        good = np.nonzero(~bad)[0]
        k = min(good.size, max(2 * n_bad, 8))
        pool = np.concatenate([np.nonzero(bad)[0], rng.choice(good, k, replace=False)])
        mixed = e[pool].reshape(-1)
        rng.shuffle(mixed)
        e[pool] = mixed.reshape(-1, 2)
    raise RuntimeError(f"no simple {d}-regular graph on {n} nodes after 500 repairs")


def mh_weights(nbr: np.ndarray):
    """(w (N, D), w_self (N,)) Metropolis-Hastings weights, float64."""
    deg = np.full(nbr.shape[0], nbr.shape[1], np.float64)
    w = 1.0 / (1.0 + np.maximum(deg[:, None], deg[nbr]))
    return w, 1.0 - w.sum(1)


# -- the rounds ---------------------------------------------------------------


class Reference:
    """D-PSGD rounds of one cell, from the seed."""

    def __init__(self, model, cfg: Dict[str, Any], dl: Dict[str, Any], data_x, data_y,
                 parts: List[np.ndarray], seed: int, dtype=jnp.float32,
                 fault: Optional[str] = None):
        self.model, self.cfg, self.dl = model, cfg, dl
        self.x, self.y = data_x, data_y
        self.lens, self.pad = padded_parts(parts)
        self.seed, self.dtype, self.fault = seed, dtype, fault
        self.n = cfg["n_nodes"]
        self.d = cfg["degree"]
        sharing = "full" if dl.get("secure") else dl.get("sharing", "full")
        if sharing not in ("full", "topk"):
            raise ValueError(f"the reference has no sharing {sharing!r}")
        self.sharing = sharing
        self.budget = dl.get("budget")
        self._static = (circulant_neighbors(self.n, self.d)
                        if dl["topology"] == "regular" else None)
        if dl["topology"] not in ("regular", "dynamic"):
            raise ValueError(f"the reference has no topology {dl['topology']!r}")
        self._step = jax.jit(self._local_step)
        self._mix = jax.jit(self._mix_full if sharing == "full" else self._mix_topk)

    def init_params(self):
        m = self.cfg["model"]
        keys = jax.random.split(jax.random.key(self.seed), self.n)
        init = lambda k: self.model.init(k, m["width"], m["channels"], m["num_classes"],
                                         dtype=self.dtype)
        return jax.jit(jax.vmap(init))(keys)

    def neighbors(self, rnd: int) -> np.ndarray:
        if self._static is not None:
            return self._static
        return random_regular_neighbors(self.n, self.d, self.seed * 100_003 + rnd)

    def batches(self, rnd: int):
        idx = batch_indices(self.seed, rnd, self.cfg["local_steps"], self.cfg["batch_size"],
                            self.lens, self.pad)
        idx = jnp.asarray(idx)
        return jnp.take(self.x, idx, axis=0), jnp.take(self.y, idx, axis=0)

    # one SGD step of every node, in blocks of nodes so the activations fit
    def _local_step(self, params, bx, by):
        loss = self.model.loss
        if self.fault == "half_batch":
            half = bx.shape[1] // 2
            loss = lambda p, x, y, f=self.model.loss: f(p, x[:half], y[:half])
        lr = jnp.asarray(self.cfg["lr"], self.dtype)

        def node(args):
            p, x, y = args
            g = jax.grad(loss)(p, x, y)
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)

        return jax.lax.map(node, (params, bx, by), batch_size=min(self.n, 64))

    def _mix_full(self, params, state, nbr, w, w_self):
        if self.fault == "no_exchange":
            return params, state
        w, w_self = w.astype(self.dtype), w_self.astype(self.dtype)

        def leaf(a):
            shape = (self.n,) + (1,) * (a.ndim - 1)
            out = w_self.reshape(shape) * a
            for s in range(nbr.shape[1]):
                out = out + w[:, s].reshape(shape) * jnp.take(a, nbr[:, s], axis=0)
            return out

        return jax.tree_util.tree_map(leaf, params), state

    def _mix_topk(self, params, last, nbr, w, w_self):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        X = jnp.concatenate([a.reshape(self.n, -1) for a in leaves], axis=1)
        k = max(1, int(self.budget * X.shape[1]))
        delta = jnp.abs(X.astype(jnp.float32) - last.astype(jnp.float32))
        idx = jax.lax.top_k(delta, k)[1]
        M = jnp.zeros(X.shape, bool).at[jnp.arange(self.n)[:, None], idx].set(True)
        new_last = jnp.where(M, X, last)
        if self.fault != "no_exchange":
            w = w.astype(self.dtype)
            out = X
            for s in range(nbr.shape[1]):
                j = nbr[:, s]
                out = out + w[:, s, None] * jnp.where(
                    jnp.take(M, j, axis=0), jnp.take(X, j, axis=0) - X, 0)
            X = out
        back, off = [], 0
        for a in leaves:
            size = int(np.prod(a.shape[1:]))
            back.append(X[:, off:off + size].reshape(a.shape))
            off += size
        return jax.tree_util.tree_unflatten(treedef, back), new_last

    def init_state(self, params):
        if self.sharing != "topk":
            return ()
        leaves = jax.tree_util.tree_leaves(params)
        return jnp.concatenate([a.reshape(self.n, -1) for a in leaves], axis=1)

    def run(self, params, state, start: int, rounds: int):
        """``rounds`` rounds from round ``start``: (params, state)."""
        for rnd in range(start, start + rounds):
            bx, by = self.batches(rnd)
            for s in range(bx.shape[0]):
                params = self._step(params, bx[s], by[s])
            nbr = self.neighbors(rnd)
            w, w_self = mh_weights(nbr)
            params, state = self._mix(params, state, jnp.asarray(nbr),
                                      jnp.asarray(w, jnp.float32),
                                      jnp.asarray(w_self, jnp.float32))
        return params, state

    def first_grad_norms(self, params, rnd: int = 0):
        """Per-leaf norms of every node's first gradient (leaf paths as keys)."""
        bx, by = self.batches(rnd)

        @jax.jit
        def norms(params, bx, by):
            g = jax.lax.map(lambda a: jax.grad(self.model.loss)(*a), (params, bx, by),
                            batch_size=min(self.n, 64))
            return jax.tree_util.tree_map(
                lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), g)

        return flat_leaves(jax.device_get(norms(params, bx[0], by[0])))


def flat_leaves(tree) -> Dict[str, Any]:
    """{"conv1/w": leaf, ...} of a parameter tree."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(p, "key", p)) for p in path)] = leaf
    return out
