"""End-to-end behaviour tests: the paper's system-level claims at mini
scale, the runners, the serving engine, and the HLO roofline parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DLConfig, DecentralizedRunner, FLConfig, FederatedRunner
from repro.data import NodeBatcher, make_dataset, sharding_partition
from repro.launch.roofline import Roofline, parse_collective_bytes
from repro.models.api import cross_entropy
from repro.models.mlp import mlp_apply, mlp_init
from repro.optim import make_optimizer


def _mlp_setup(n_nodes=8, n_train=1024, bs=16):
    ds = make_dataset("cifar10", n_train=n_train, n_test=256, sigma=0.8)
    parts = sharding_partition(ds.train_y, n_nodes, 2, seed=0)
    batcher = NodeBatcher(ds.train_x, ds.train_y, parts, bs, seed=0)

    def loss_fn(p, x, y):
        return cross_entropy(mlp_apply(p, x), y)

    def acc_fn(p, x, y):
        return (mlp_apply(p, x).argmax(-1) == y).mean()

    init = lambda k: mlp_init(k, hidden=64)
    return init, loss_fn, acc_fn, batcher


class TestDecentralizedRunner:
    def test_dpsgd_learns(self):
        init, loss, acc, batcher = _mlp_setup()
        dl = DLConfig(n_nodes=8, topology="regular", degree=4, rounds=30,
                      eval_every=29, local_steps=1)
        r = DecentralizedRunner(dl, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
        hist = r.run(log=False)
        assert hist[-1]["acc_mean"] > 0.5, hist

    def test_denser_topology_not_worse(self):
        """Paper Fig. 3a ordering at mini scale: fully >= ring after equal
        rounds (non-IID)."""
        accs = {}
        for topo in ("ring", "fully"):
            init, loss, acc, batcher = _mlp_setup()
            dl = DLConfig(n_nodes=8, topology=topo, rounds=25, eval_every=24,
                          local_steps=1, seed=2)
            r = DecentralizedRunner(dl, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
            accs[topo] = r.run(log=False)[-1]["acc_mean"]
        assert accs["fully"] >= accs["ring"] - 0.02, accs

    def test_bytes_accounting_scales_with_degree(self):
        init, loss, acc, batcher = _mlp_setup()
        byt = {}
        for topo, deg in (("ring", 2), ("fully", 7)):
            dl = DLConfig(n_nodes=8, topology=topo, rounds=3, eval_every=2)
            r = DecentralizedRunner(dl, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
            r.run(log=False)
            byt[topo] = r.bytes_sent
        assert byt["fully"] / byt["ring"] == pytest.approx(7 / 2, rel=1e-6)

    def test_dynamic_topology_runs(self):
        init, loss, acc, batcher = _mlp_setup()
        dl = DLConfig(n_nodes=8, topology="dynamic", degree=3, rounds=5, eval_every=4)
        r = DecentralizedRunner(dl, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
        hist = r.run(log=False)
        assert len(hist) >= 1

    def test_sparsified_sharing_runs_and_saves_bytes(self):
        init, loss, acc, batcher = _mlp_setup()
        dl_full = DLConfig(n_nodes=8, topology="regular", degree=4, rounds=4, eval_every=3)
        dl_rk = DLConfig(n_nodes=8, topology="regular", degree=4, rounds=4,
                         eval_every=3, sharing="randomk", budget=0.1)
        rf = DecentralizedRunner(dl_full, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
        rf.run(log=False)
        rk = DecentralizedRunner(dl_rk, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
        rk.run(log=False)
        assert rk.bytes_sent < 0.25 * rf.bytes_sent

    def test_secure_agg_matches_plain_accuracy_trajectory(self):
        init, loss, acc, batcher = _mlp_setup()
        dl_p = DLConfig(n_nodes=8, topology="regular", degree=4, rounds=10,
                        eval_every=9, seed=5)
        dl_s = DLConfig(n_nodes=8, topology="regular", degree=4, rounds=10,
                        eval_every=9, seed=5, secure=True)
        rp = DecentralizedRunner(dl_p, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
        hp = rp.run(log=False)
        rs = DecentralizedRunner(dl_s, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
        hs = rs.run(log=False)
        assert abs(hp[-1]["acc_mean"] - hs[-1]["acc_mean"]) < 0.06
        assert rs.bytes_sent == pytest.approx(1.03 * rp.bytes_sent, rel=1e-6)

    def test_results_json_written(self, tmp_path):
        init, loss, acc, batcher = _mlp_setup()
        dl = DLConfig(n_nodes=8, rounds=2, eval_every=1, results_dir=str(tmp_path))
        r = DecentralizedRunner(dl, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
        r.run(log=False)
        assert (tmp_path / "results.json").exists()


class TestFederatedRunner:
    def test_fedavg_learns(self):
        init, loss, acc, batcher = _mlp_setup()
        fl = FLConfig(n_clients=8, clients_per_round=4, rounds=40, eval_every=39)
        r = FederatedRunner(fl, init, loss, acc, make_optimizer("sgd", 0.05), batcher)
        hist = r.run(log=False)
        assert hist[-1]["acc"] > 0.5


class TestServingEngine:
    def test_generate(self):
        from repro.models import ModelConfig
        from repro.models.api import init_params
        from repro.serving import ServeConfig, ServingEngine

        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                          n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
        params = init_params(cfg, jax.random.key(0))
        eng = ServingEngine(cfg, ServeConfig(batch=2, max_len=32, eos_id=0), params)
        prompts = jax.random.randint(jax.random.key(1), (2, 4), 1, 64)
        out = eng.generate(prompts, max_new=6)
        assert out.shape == (2, 6)
        assert bool((out >= 0).all())


class TestRooflineParser:
    def test_parse_known_collectives(self):
        """Compile a module with a known psum + ppermute and check the
        parser finds the right byte counts."""
        import subprocess, sys, textwrap

        script = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.launch.roofline import parse_collective_bytes
            from jax import shard_map
            mesh = jax.make_mesh((4,), ("d",))
            def f(x):
                y = jax.lax.psum(x, "d")
                z = jax.lax.ppermute(x, "d", [(i, (i+1) % 4) for i in range(4)])
                return y + z
            fn = shard_map(f, mesh=mesh, in_specs=P(None), out_specs=P(None),
                               check_vma=False)
            x = jax.ShapeDtypeStruct((1024,), jnp.float32)
            hlo = jax.jit(fn).lower(x).compile().as_text()
            c = parse_collective_bytes(hlo)
            assert c["all-reduce"] == 4096, c
            assert c["collective-permute"] == 4096, c
            assert c["count"] >= 2
            print("PARSE_OK")
        """)
        r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                           text=True, timeout=300)
        assert "PARSE_OK" in r.stdout, r.stdout + r.stderr

    def test_roofline_terms(self):
        r = Roofline(
            arch="a", shape="s", mesh="16x16", flops_dev=197e12,
            hbm_bytes_dev=819e9, coll_bytes_dev=50e9, coll_breakdown={},
            model_flops_total=197e12 * 256, n_chips=256,
        )
        assert r.t_compute == pytest.approx(1.0)
        assert r.t_memory == pytest.approx(1.0)
        assert r.t_collective == pytest.approx(1.0)
        assert r.useful_flops_ratio == pytest.approx(1.0)
        r2 = Roofline(arch="a", shape="s", mesh="16x16", flops_dev=1e12,
                      hbm_bytes_dev=819e9 * 5, coll_bytes_dev=1e9,
                      coll_breakdown={}, model_flops_total=1e12, n_chips=256)
        assert r2.bottleneck == "memory"
