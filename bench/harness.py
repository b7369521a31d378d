"""Loading a cell by name, and building its inputs and the system under test.

A cell is ``bench/workloads/<cell>.json``: the names of its configuration
(``bench/configs/<config>.json``) and of its traffic mix
(``bench/traffic/<traffic>.json``), the chips it needs, and the limits of
the comparison that decides ``correct``.  Adding a cell is adding files;
nothing here names a cell, a configuration or a mix.

The inputs are made here from the seed, never by the program: the
synthetic CIFAR-10 stand-in (one class prototype per label plus unit
noise, made on the device in one jitted call) and the 2-shard non-IID
partition (sort by label, cut into 2N shards, deal two to each node).
The program gets them as arrays, with the seed for what it draws itself
(its batch order, its dynamic graphs, its initial weights).
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    limits: Dict[str, float]


def _read(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: Path = BENCH) -> Cell:
    """The cell ``name`` from its file and the files it names."""
    spec = _read(bench / "workloads" / f"{name}.json")
    config = _read(bench / "configs" / f"{spec['config']}.json")
    traffic = _read(bench / "traffic" / f"{spec['traffic']}.json")
    chips = int(spec["chips"])
    if chips not in (1, 4):
        raise ValueError(f"cell {name}: chips must be 1 or 4, not {chips}")
    return Cell(name, config, traffic, chips, dict(spec.get("limits", {})))


def cell_names(bench: Path = BENCH) -> List[str]:
    return sorted(p.stem for p in (bench / "workloads").glob("*.json"))


def load_module(kind: str, name: str, bench: Path = BENCH):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = bench / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry_point(path: str) -> Callable:
    """``"package.module:attr"`` -> the program's attribute."""
    module, attr = path.split(":")
    return getattr(importlib.import_module(module), attr)


# -- inputs ------------------------------------------------------------------


def make_dataset(data: Dict[str, Any], seed: int):
    """(x (n, H, W, C) float32, y (n,) int32) on the default device: label
    prototypes plus ``sigma`` unit noise, scaled back to unit variance."""
    import jax
    import jax.numpy as jnp

    n, shape, classes = data["n_train"], tuple(data["image_shape"]), data["num_classes"]
    sigma = float(data["sigma"])

    @jax.jit
    def build(key):
        kp, ky, kx = jax.random.split(key, 3)
        protos = jax.random.normal(kp, (classes, *shape), jnp.float32)
        y = jax.random.randint(ky, (n,), 0, classes, jnp.int32)
        noise = jax.random.normal(kx, (n, *shape), jnp.float32)
        x = (protos[y] + sigma * noise) / np.sqrt(1.0 + sigma ** 2)
        return x, y

    return build(jax.random.key(seed))


def shard_partition(labels: np.ndarray, n_nodes: int, shards: int, seed: int):
    """The 2-shard non-IID partition (McMahan et al.): sort by label, cut
    into ``n_nodes * shards`` contiguous shards, deal ``shards`` at random
    to each node.  Returns one sorted index array per node."""
    rng = np.random.default_rng(seed)
    order = np.argsort(labels, kind="stable")
    cut = np.array_split(order, n_nodes * shards)
    deal = rng.permutation(n_nodes * shards)
    return [np.sort(np.concatenate([cut[s] for s in deal[i * shards:(i + 1) * shards]]))
            for i in range(n_nodes)]


@dataclasses.dataclass
class Inputs:
    x: Any                 # (n, H, W, C) float32, on the device
    y: Any                 # (n,) int32, on the device
    parts: List[np.ndarray]


def make_inputs(cell: Cell, seed: int) -> Inputs:
    import jax

    cfg = cell.config
    x, y = make_dataset(cfg["data"], seed)
    labels = np.asarray(jax.device_get(y))
    parts = shard_partition(labels, cfg["n_nodes"], cfg["data"]["shards_per_node"], seed)
    return Inputs(x, y, parts)


# -- the system under test ---------------------------------------------------


def model_fns(cfg: Dict[str, Any]):
    """(init(key), loss(params, x, y)) of the program's model, from the
    entry points the configuration names."""
    m = cfg["model"]
    init, apply, loss = (entry_point(m[k]) for k in ("init", "apply", "loss"))

    def init_fn(key):
        return init(key, num_classes=m["num_classes"], channels=m["channels"],
                    width=m["width"])

    def loss_fn(params, x, y):
        return loss(apply(params, x), y)

    return init_fn, loss_fn


def build_engine(cell: Cell, inputs: Inputs, seed: int,
                 loss_wrap: Optional[Callable] = None):
    """The program's RoundEngine for this cell, built the way
    ``examples/quickstart.py`` builds one.  ``loss_wrap`` lets a test put
    a broken loss underneath (it is never set by a benchmark run)."""
    from repro.core import DLConfig, RoundEngine
    from repro.data import NodeBatcher
    from repro.optim import make_optimizer

    cfg = cell.config
    init_fn, loss_fn = model_fns(cfg)
    if loss_wrap is not None:
        loss_fn = loss_wrap(loss_fn)
    batcher = NodeBatcher(inputs.x, inputs.y, inputs.parts, cfg["batch_size"], seed=seed)
    dl = DLConfig(
        n_nodes=cfg["n_nodes"], degree=cfg["degree"], batch_size=cfg["batch_size"],
        local_steps=cfg["local_steps"], chunk_rounds=cfg["chunk_rounds"],
        seed=seed, **cell.traffic["dl"])
    # no evaluation runs in the benchmark: the loss stands in for accuracy
    return RoundEngine(dl, init_fn, loss_fn, loss_fn,
                       make_optimizer(cfg["optimizer"], cfg["lr"]), batcher)
