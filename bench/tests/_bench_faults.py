"""Faults planted under the timed path, for the per-cell fault tests."""
from _bench_path import BENCH  # noqa: F401

import run as bench_run  # noqa: E402


def _unchanged(eng):
    """A step that returns the state it was given."""
    step = eng.steps.train_and_mix

    def same(params, opt_state, share_state, *a, **k):
        return (params, opt_state, share_state) + step(params, opt_state, share_state, *a, **k)[3:]

    eng.steps.train_and_mix = same


def _half_batch(loss):
    """The mean loss over half of each batch."""
    def half(p, x, y):
        return loss(p, x[: x.shape[0] // 2], y[: y.shape[0] // 2])

    return half


class _NoExchange:
    """The sharing stage with the exchange left out: every node keeps its own row."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def round(self, X, *a, **k):
        _, state, nbytes = self.inner.round(X, *a, **k)
        return X, state, nbytes


def _no_exchange(eng):
    eng.steps.sharing = _NoExchange(eng.steps.sharing)


FAULTS = {
    "unchanged_state": bench_run.Tamper(engine=_unchanged),
    "half_batch": bench_run.Tamper(loss_wrap=_half_batch),
    "no_exchange": bench_run.Tamper(engine=_no_exchange),
}


def run_tiny(cell, tamper=None):
    """One whole run of ``cell`` at TINY sizes on this backend."""
    return bench_run.run(cell, seed=2147483647 + 12, seconds=0.2, trace=0,
                         require_chip=False, rehearse=True, tamper=tamper)
