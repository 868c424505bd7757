"""What one cell is made of, found by name: its entry in ``BENCHMARK.json``,
its configuration file, its traffic file and its limits file, and the
graph its traffic file describes.

A traffic file of a training cell states a graph: the public dataset it
is shaped like (``published``), and the parameters of the one generator
here that makes it from its own ``data_seed``: an R-MAT edge list, made
undirected, labels by majority propagation over the edges, features as
class centres plus noise, smoothed over one hop.  The generator is the
repository's ``graph/generate.py`` (``rmat_graph``,
``community_labels_and_features``, ``train_val_test_split``) rewritten
with sparse products in place of ``np.add.at``; the graph is fixed by the
traffic file alone, never by the run's ``--seed``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import numpy as np
import scipy.sparse as sp

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as written
    traffic: dict         # the traffic file, as written
    limits: dict          # number compared -> limit
    bench: dict           # the whole of BENCHMARK.json
    dir: str              # the benchmark's directory

    @property
    def end_to_end(self) -> list:
        return self.bench["end_to_end"]

    @property
    def per_layer(self) -> list:
        return self.bench["per_layer"]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = os.path.join(root, bench["paths"][0])
    return Cell(
        name=name, chips=int(w["chips"]), bench=bench, dir=here,
        config=_read_json(os.path.join(root, conf["file"])),
        traffic=_read_json(os.path.join(here, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(here, "limits", name + ".json")))


# ---- the graph -------------------------------------------------------------

def rmat_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
               rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """R-MAT: ``edge_factor * 2**scale`` draws, ids permuted, self loops
    and duplicates dropped, then made undirected (each edge both ways)."""
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for level in range(scale):
        r = rng.random(m)
        go_right = r >= a + b
        go_down = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src |= go_down.astype(np.int64) << level
        dst |= go_right.astype(np.int64) << level
    perm = rng.permutation(n)
    src, dst = perm[src], perm[dst]
    keep = src != dst
    key = np.unique(np.concatenate([src[keep] * n + dst[keep],
                                    dst[keep] * n + src[keep]]))
    return key // n, key % n


def labels_and_features(src: np.ndarray, dst: np.ndarray, n: int,
                        num_classes: int, dim: int,
                        rng: np.random.Generator, noise: float = 1.0):
    """Three rounds of majority propagation from random labels, then
    class-centred Gaussian features averaged half-and-half with their
    in-neighbours' mean: a task that rewards aggregating neighbours."""
    adj = sp.csr_matrix((np.ones(len(src), np.float32), (dst, src)),
                        shape=(n, n))
    labels = rng.integers(0, num_classes, size=n).astype(np.int64)
    rows = np.arange(n)
    for _ in range(3):
        onehot = np.zeros((n, num_classes), dtype=np.float32)
        onehot[rows, labels] = 1.0
        agg = np.asarray(adj @ onehot)
        agg += onehot * 0.5 + rng.random((n, num_classes)) * 0.1
        labels = agg.argmax(axis=1).astype(np.int64)
    centers = rng.standard_normal((num_classes, dim)).astype(np.float32)
    feats = centers[labels] + noise * rng.standard_normal(
        (n, dim)).astype(np.float32)
    deg = np.maximum(np.diff(adj.indptr), 1).astype(np.float32)
    smooth = np.asarray(adj @ feats)
    return labels, (0.5 * feats + 0.5 * smooth / deg[:, None]).astype(
        np.float32)


def split_mask(n: int, train_frac: float, val_frac: float,
               rng: np.random.Generator) -> np.ndarray:
    """1 train / 2 validation / 3 test / 0 unlabelled, drawn by one
    permutation."""
    perm = rng.permutation(n)
    n_tr, n_va = int(n * train_frac), int(n * val_frac)
    mask = np.zeros(n, dtype=np.int8)
    mask[perm[:n_tr]] = 1
    mask[perm[n_tr:n_tr + n_va]] = 2
    mask[perm[n_tr + n_va:n_tr + n_va + n_tr]] = 3
    return mask


@dataclasses.dataclass
class Graph:
    """The generated graph in plain arrays (original node ids)."""
    src: np.ndarray        # (m,) int64, both directions of every edge
    dst: np.ndarray
    feats: np.ndarray      # (n, d) float32
    labels: np.ndarray     # (n,) int64
    split: np.ndarray      # (n,) int8
    num_classes: int

    @property
    def num_nodes(self) -> int:
        return len(self.labels)

    def edge_keys(self) -> np.ndarray:
        """Sorted ``src * n + dst`` of every edge, for membership tests."""
        return np.sort(self.src * self.num_nodes + self.dst)


def make_graph(traffic: dict) -> Graph:
    if traffic["generator"] != "rmat":
        raise SystemExit(f"unknown generator {traffic['generator']!r}")
    rng = np.random.default_rng(int(traffic["data_seed"]))
    n = 1 << int(traffic["scale"])
    src, dst = rmat_edges(int(traffic["scale"]), int(traffic["edge_factor"]),
                          float(traffic["a"]), float(traffic["b"]),
                          float(traffic["c"]), rng)
    labels, feats = labels_and_features(src, dst, n,
                                        int(traffic["num_classes"]),
                                        int(traffic["feat_dim"]), rng)
    split = split_mask(n, float(traffic["train_frac"]),
                       float(traffic["val_frac"]), rng)
    return Graph(src=src, dst=dst, feats=feats, labels=labels, split=split,
                 num_classes=int(traffic["num_classes"]))


def as_dataset(g: Graph, name: str):
    """The program's input object for the same graph."""
    from repro.graph.csr import from_edges
    from repro.graph.datasets import GraphDataset
    return GraphDataset(name=name, graph=from_edges(g.src, g.dst,
                                                    g.num_nodes),
                        feats=g.feats, labels=g.labels, split_mask=g.split,
                        num_classes=g.num_classes)


# ---- the model and the job -------------------------------------------------

def model_config(config: dict, g: Graph):
    """The program's ``GNNConfig``: the configuration file's sizes, with
    ``in_dim`` and ``num_classes`` from the graph."""
    from repro.models.gnn import GNNConfig
    return GNNConfig(arch=config["arch"], in_dim=int(g.feats.shape[1]),
                     hidden_dim=int(config["hidden_dim"]),
                     num_classes=g.num_classes,
                     fanouts=[int(f) for f in config["fanouts"]],
                     batch_size=int(config["batch_size"]),
                     num_heads=int(config.get("num_heads", 1)))


def job_config(config: dict, seed: int):
    """The job a user gets by default, at the configuration's layout and
    learning rate: async non-stop pipelines, packed staging, ``impl``
    from the platform, one sampling worker, no cache, no simulated
    network, no fault injector."""
    from repro.api import TrainJobConfig
    return TrainJobConfig(
        num_machines=int(config["num_machines"]),
        trainers_per_machine=int(config["trainers_per_machine"]),
        lr=float(config["lr"]), seed=int(seed))


def capacities(batch_size: int, fanouts: list) -> list:
    """Static ``(cap_dst, cap_edge, cap_src)`` per layer, input layer
    first: ``cap_dst`` of the last layer is the batch, each layer's
    ``cap_edge = cap_dst * fanout`` and ``cap_src = cap_dst + cap_edge``,
    which is the next layer inward's ``cap_dst``."""
    out = []
    cap_dst = int(batch_size)
    for f in reversed([int(x) for x in fanouts]):
        cap_edge = cap_dst * f
        out.append((cap_dst, cap_edge, cap_dst + cap_edge))
        cap_dst += cap_edge
    return out[::-1]
