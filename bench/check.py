"""The comparison that decides ``correct``.

The window's own compiled chunk drives the cell from the seed through its
first chunk of rounds during set-up; the parameters it leaves are the
candidate.  The plain reference (``reference.py`` with the configuration's
model reference) runs the same rounds from the same seed in float32 at
HIGHEST precision, after the window, and four numbers are compared:

``change_gap``: for each parameter leaf (stacked over the nodes), the gap
between the norm of the candidate's change from the reference's initial
weights and the norm of the reference's change, over the reference's
change norm of that leaf or of the median leaf, whichever is larger; the
worst leaf counts.  Leaves whose reference first gradient is under a
thousandth of the median leaf's are left out (they move by round-off).

``median_gap``: the same gaps' median over the leaves — steadier than the
worst leaf where one small leaf (a bias that GroupNorm all but cancels)
carries the precision noise.

``spread_gap``: as ``change_gap``, for the norm of each leaf's change
about its mean over the nodes — how far the nodes stand apart, which
gossip shrinks and each node's own batches widen.

``lead_spread_gap``: that spread gap for one leaf, chosen by a rule on
the reference: of the leaves that every node starts from the same values
(biases, GroupNorm gains and shifts; elsewhere the spread is the nodes'
different initial draws, which no fault moves), the one with the largest
first gradient per entry (the output layer's bias).  Its rounding is the
smallest share of its change, so its spread reads how much batch noise
each node's step carries, where the worst leaf reads the program's
precision noise in a bias under GroupNorm.

``loss_gap``: the relative gap between the mean loss of the candidate's
and of the reference's final weights, each node on its batch of the next
round, both evaluated by the reference model.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference import Reference, flat_leaves

NUMBERS = ("change_gap", "median_gap", "spread_gap", "lead_spread_gap", "loss_gap")


def _node_sq(a, b):
    """Per-node sums of squares of a - b, (N,) float32."""
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    return jnp.sum(jnp.square(d.reshape(d.shape[0], -1)), axis=1)


_node_sq_jit = jax.jit(_node_sq)


@jax.jit
def _spread_sq(a, b):
    """Squared norm of a - b about its mean over the nodes: how far the
    nodes' changes stand apart."""
    d = a.astype(jnp.float32) - b.astype(jnp.float32)
    d = d.reshape(d.shape[0], -1)
    return jnp.sum(jnp.square(d - jnp.mean(d, axis=0)))


class Judge:
    """The float32 reference of one cell and seed, and the numbers of any
    candidate end state against it."""

    def __init__(self, model, cfg: Dict[str, Any], dl: Dict[str, Any], inputs, seed: int):
        self.model, self.cfg, self.dl, self.inputs, self.seed = model, cfg, dl, inputs, seed
        self.rounds = cfg["chunk_rounds"]
        ref = self.reference()
        self.x0 = ref.init_params()
        self.grad_norms = ref.first_grad_norms(self.x0)
        self.x_ref, _ = ref.run(self.x0, ref.init_state(self.x0), 0, self.rounds)
        self._eval_batch = ref.batches(self.rounds)
        self.ref_change = self._change_norms(self.x_ref)
        self.ref_spread = self._spread_norms(self.x_ref)
        self.ref_loss = self._loss(self.x_ref)
        med = float(np.median(list(self.grad_norms.values())))
        self.leaves = sorted(k for k, g in self.grad_norms.items() if float(g) >= 1e-3 * med)
        self.skipped = sorted(set(self.grad_norms) - set(self.leaves))
        x0 = flat_leaves(self.x0)
        self.equal_start = [k for k in self.leaves
                            if float(_spread_sq(x0[k], jnp.zeros_like(x0[k]))) == 0.0]
        self.lead = max(self.equal_start, default=None,
                        key=lambda k: float(self.grad_norms[k]) / np.sqrt(x0[k].size))

    def reference(self, dtype=jnp.float32, fault: Optional[str] = None) -> Reference:
        return Reference(self.model, self.cfg, self.dl, self.inputs.x, self.inputs.y,
                         self.inputs.parts, self.seed, dtype=dtype, fault=fault)

    def control(self, dtype=jnp.bfloat16, fault: Optional[str] = None):
        """End state of the reference run in ``dtype`` and/or with a
        planted fault, put in the program's place."""
        ref = self.reference(dtype, fault)
        x0 = ref.init_params()
        x, _ = ref.run(x0, ref.init_state(x0), 0, self.rounds)
        return x

    def _change_sq(self, tree) -> Dict[str, np.ndarray]:
        """Per leaf, each node's squared norm of its change from the
        reference's initial weights, (N,) float64."""
        x0 = flat_leaves(self.x0)
        return {k: np.asarray(jax.device_get(_node_sq_jit(jnp.asarray(a), x0[k])), np.float64)
                for k, a in flat_leaves(tree).items()}

    def _change_norms(self, tree) -> Dict[str, float]:
        return {k: float(np.sqrt(sq.sum())) for k, sq in self._change_sq(tree).items()}

    def _spread_norms(self, tree) -> Dict[str, float]:
        x0 = flat_leaves(self.x0)
        return {k: float(np.sqrt(float(_spread_sq(jnp.asarray(a), x0[k]))))
                for k, a in flat_leaves(tree).items()}

    def _loss(self, tree) -> float:
        bx, by = self._eval_batch
        params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)
        f = jax.jit(lambda p, x, y: jax.lax.map(
            lambda a: self.model.loss(*a), (p, x, y), batch_size=min(self.cfg["n_nodes"], 64)))
        return float(np.mean(np.asarray(jax.device_get(f(params, bx[0], by[0])), np.float64)))

    def _gaps(self, ours: Dict[str, float], ref: Dict[str, float]) -> Dict[str, float]:
        med = float(np.median([ref[k] for k in self.leaves]))
        return {k: abs(ours[k] - ref[k]) / max(ref[k], med) for k in self.leaves}

    def numbers(self, tree) -> Dict[str, float]:
        changes, spreads = self._change_norms(tree), self._spread_norms(tree)
        gaps = self._gaps(changes, self.ref_change)
        spread = self._gaps(spreads, self.ref_spread)
        loss = self._loss(tree)
        return {"change_gap": max(gaps.values()),
                "median_gap": float(np.median(list(gaps.values()))),
                "spread_gap": max(spread.values()),
                "lead_spread_gap": spread[self.lead] if self.lead else float("nan"),
                "loss_gap": abs(loss - self.ref_loss) / abs(self.ref_loss),
                "worst_leaf": max(gaps, key=gaps.get), "leaf_gaps": gaps,
                "leaf_spread_gaps": spread, "leaf_changes": changes, "leaf_spreads": spreads}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]], List[str]]:
    """(correct, {name: {value, limit}}, lines): every number that has a
    limit must be at or under it; a cell with no limits is not correct."""
    shown = {k: {"value": float(numbers[k]), "limit": float(limits[k])}
             for k in NUMBERS if k in limits}
    ok = bool(shown) and all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
                             for v in shown.values())
    lines = [f"check {k}: {v['value']!r} (limit {v['limit']!r})" for k, v in shown.items()]
    return ok, shown, lines
