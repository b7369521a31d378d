"""Fault-tolerant gossip — message-level fault injection end-to-end.

Sweeps ``FaultPlan.msg_loss`` against churn and secure aggregation (with
the Bonawitz seed-recovery pass) and prints the traced fault counters each
configuration accumulated in its history records:

* ``msg_loss``: each directed message is lost independently per round;
  the mixing operand renormalizes (rows stay stochastic), the sender
  still pays wire bytes and link time.  Pure loss is *survived by
  design* — counters show injected == survived, detected == 0.
* ``--corrupt``: post-mix payload corruption (NaN bursts); the step
  guard detects the non-finite rows and rolls them back to the
  last-good snapshot — injected == detected == recovered.
* ``--crash N:D:R``: declarative crash/restart windows (node N down for
  rounds [D, R); R=-1 means forever) that AND into the churn mask.
* ``--secure``: secure aggregation stays exact under churn via
  ``secure_recovery=True`` (dropped pairs' PRF masks are re-derived by
  surviving co-neighbors and subtracted); the seed-share traffic shows
  up as ``recovery_bytes``.

    PYTHONPATH=src python examples/faults.py --rounds 40
    PYTHONPATH=src python examples/faults.py --participation 0.7 --secure
    PYTHONPATH=src python examples/faults.py --corrupt 0.05 --crash 3:5:12
"""
import argparse

from repro.core import DLConfig, FaultPlan, RoundEngine
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.models.api import cross_entropy
from repro.models.mlp import mlp_apply, mlp_init
from repro.optim import make_optimizer
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--secure", action="store_true",
                    help="secure aggregation + Bonawitz seed recovery "
                         "(composes with churn/crashes, not msg_loss)")
    ap.add_argument("--corrupt", type=float, default=0.0,
                    help="per-node payload corruption probability")
    ap.add_argument("--crash", action="append", default=[],
                    metavar="N:D:R", help="crash node N for rounds [D, R)")
    args = ap.parse_args()

    crashes = tuple(tuple(int(v) for v in c.split(":")) for c in args.crash)

    ds = make_dataset("cifar10", n_train=8192, n_test=512)
    parts = sharding_partition(ds.train_y, args.nodes, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, 8, seed=0)

    loss_fn = lambda p, x, y: cross_entropy(mlp_apply(p, x), y)
    acc_fn = lambda p, x, y: (mlp_apply(p, x).argmax(-1) == y).mean()

    losses = (0.0,) if args.secure else (0.0, 0.05, 0.1, 0.2)
    print(f"{'msg_loss':>9s} {'acc':>8s} {'sim LAN s':>10s} {'injected':>9s} "
          f"{'detected':>9s} {'survived':>9s} {'recovered':>10s} "
          f"{'recovery MB':>12s}")
    for p_loss in losses:
        plan = None
        if p_loss > 0 or args.corrupt > 0 or crashes:
            plan = FaultPlan(msg_loss=p_loss, corrupt_prob=args.corrupt,
                             crashes=crashes)
        dl = DLConfig(n_nodes=args.nodes, topology="regular", degree=5,
                      rounds=args.rounds, eval_every=args.rounds - 1,
                      local_steps=2, participation=args.participation,
                      network="lan", compute_time_s=0.05, faults=plan,
                      secure=args.secure,
                      secure_recovery=args.secure)
        e = RoundEngine(dl, lambda k: mlp_init(k, hidden=128), loss_fn,
                        acc_fn, make_optimizer("sgd", 0.05), batcher)
        hist = e.run(log=False)
        rec = hist[-1]
        print(f"{p_loss:9.2f} {rec['acc_mean']:8.4f} {e.sim_time_s:10.2f} "
              f"{rec.get('faults_injected', 0):9d} "
              f"{rec.get('faults_detected', 0):9d} "
              f"{rec.get('faults_survived', 0):9d} "
              f"{rec.get('faults_recovered', 0):10d} "
              f"{rec.get('recovery_bytes', 0.0) / 1e6:12.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
