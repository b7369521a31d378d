"""Quickstart — the paper's Fig. 2 node loop, in this framework.

16 nodes, 5-regular static topology, GN-LeNet on the synthetic CIFAR-10
stand-in with 2-sharding non-IID data, plain SGD (the paper's recipe).

Execution goes through the RoundEngine: chunks of rounds are compiled into
a single ``lax.scan`` (batches gathered from the device-resident dataset,
per-round metrics collected on device), so the emulation runs as fast as
the hardware allows.  Optionally attach a simulated network (--network lan)
to also get the paper's simulated wall-clock axis.

    PYTHONPATH=src python examples/quickstart.py [--rounds 60]
"""
import argparse

from repro.core import DLConfig, RoundEngine
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.models.api import cross_entropy
from repro.models.cnn import cnn_apply, cnn_init
from repro.optim import make_optimizer
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=10,
                    help="rounds per compiled scan chunk (0 = legacy per-round)")
    ap.add_argument("--network", default="none", choices=["none", "lan", "wan"],
                    help="simulated deployment for the wall-clock axis")
    ap.add_argument("--shard-devices", type=int, default=0,
                    help="shard the node axis over this many devices (CPU: "
                         "set XLA_FLAGS=--xla_force_host_platform_device_count)")
    args = ap.parse_args()

    # Dataset module: read, partition (non-IID 2-sharding), evaluate.
    ds = make_dataset("cifar10", n_train=8192, n_test=512)
    parts = sharding_partition(ds.train_y, args.nodes, shards_per_node=2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, batch_size=8, seed=0)

    # Training module: loss/metric over the Model module.
    def loss_fn(p, x, y):
        return cross_entropy(cnn_apply(p, x), y)

    def acc_fn(p, x, y):
        return (cnn_apply(p, x).argmax(-1) == y).mean()

    # Node + Graph + Sharing + Communication, one config object.
    dl = DLConfig(
        n_nodes=args.nodes,
        topology="regular", degree=5,   # Graph module
        sharing="full",                 # Sharing module (D-PSGD full sharing)
        local_steps=2, rounds=args.rounds, eval_every=10,
        chunk_rounds=args.chunk,        # rounds per compiled lax.scan
        network=args.network,           # NetworkModel (simulated time)
        shard_devices=args.shard_devices,  # node axis over a device mesh
        results_dir="results/quickstart",
    )
    engine = RoundEngine(
        dl, lambda k: cnn_init(k, width=16), loss_fn, acc_fn,
        make_optimizer("sgd", 0.05), batcher,
    )
    hist = engine.run()
    print(f"\nfinal: acc {hist[-1]['acc_mean']:.4f} ± {hist[-1]['acc_std']:.4f}, "
          f"{engine.bytes_sent / 1e6:.1f} MB sent/node "
          + (f"simulated {engine.sim_time_s:.1f}s on {args.network}, "
             if args.network != "none" else "")
          + "(results in results/quickstart/results.json)")


if __name__ == "__main__":
    enable_compile_cache()
    main()
