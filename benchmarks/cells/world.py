"""What one cell is made of, found by name: its entry in ``BENCHMARK.json``,
its configuration file, its traffic file and its limits file, and the
graph its traffic file describes.

A traffic file of a training cell states a graph: the public dataset it
is shaped like (``published``), and the parameters of one of the two
generators here that make it from its own ``data_seed``:

* ``"rmat"``: one node type and one relation.  An R-MAT edge list, made
  undirected, labels by majority propagation over the edges, features as
  class centres plus noise, smoothed over one hop.  This is the
  repository's ``graph/generate.py`` (``rmat_graph``,
  ``community_labels_and_features``, ``train_val_test_split``) rewritten
  with sparse products in place of ``np.add.at``.
* ``"typed"``: a heterogeneous graph (:func:`make_typed_graph`): node types
  counted from one ``scale`` by their published ratios, relations as
  canonical ``(src_type, relation, dst_type)`` triples in message
  direction, labels and the split on the target type alone, and every
  other type's features the mean of its neighbours' rows.

The graph is fixed by the traffic file alone, never by the run's
``--seed``.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

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
    """The generated graph in plain arrays (original node ids).  A typed
    graph puts its node types contiguously in declaration order, its
    edges relation by relation, and names them in ``schema``; an R-MAT
    graph leaves ``ntypes``, ``etypes`` and ``schema`` None."""
    src: np.ndarray        # (m,) int64, message direction src -> dst
    dst: np.ndarray
    feats: np.ndarray      # (n, d) float32
    labels: np.ndarray     # (n,) int64; -1 off the target type
    split: np.ndarray      # (n,) int8
    num_classes: int
    ntypes: Optional[np.ndarray] = None    # (n,) int32 node type ids
    etypes: Optional[np.ndarray] = None    # (m,) int32 relation ids
    schema: Optional[dict] = None          # {"ntypes": (...),
                                           #  "relations": ((s, r, d), ...)}

    @property
    def num_nodes(self) -> int:
        return len(self.labels)

    @property
    def relations(self) -> Optional[list]:
        """Relation names in id order; None on an untyped graph."""
        if self.schema is None:
            return None
        return [r for _, r, _ in self.schema["relations"]]

    def edge_keys(self) -> np.ndarray:
        """Sorted ``src * n + dst`` of every edge, for membership tests;
        on a typed graph ``(relation * n + src) * n + dst``."""
        n = self.num_nodes
        if self.etypes is None:
            return np.sort(self.src * n + self.dst)
        if len(self.relations) * n * n >= 2**63:
            raise ValueError(f"{n} nodes: typed edge keys overflow int64")
        return np.sort((self.etypes.astype(np.int64) * n + self.src) * n
                       + self.dst)


def make_graph(traffic: dict) -> Graph:
    if traffic["generator"] == "typed":
        return make_typed_graph(traffic)
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


# ---- the typed graph -------------------------------------------------------

def skewed_ids(count: int, n: int, skew: float,
               rng: np.random.Generator) -> np.ndarray:
    """``count`` ids in ``[0, n)``: uniform at ``skew`` 0, else drawn with
    a Zipf-like skew (rank ``n * u ** (1 / (1 - skew))``, ranks permuted
    so that hubs are spread over the ids), the degree profile of
    citation and authorship graphs."""
    if skew <= 0:
        return rng.integers(0, n, size=count)
    ranks = (n * rng.random(count) ** (1.0 / (1.0 - skew))).astype(np.int64)
    return rng.permutation(n)[np.minimum(ranks, n - 1)]


def _simple(src: np.ndarray, dst: np.ndarray, n_dst: int,
            same_type: bool) -> tuple[np.ndarray, np.ndarray]:
    """One relation's edges with self-loops (within one type) and
    duplicates dropped, sorted by ``(src, dst)``."""
    if same_type:
        keep = src != dst
        src, dst = src[keep], dst[keep]
    key = np.unique(src * n_dst + dst)
    return key // n_dst, key % n_dst


def _neighbour_mean(src: np.ndarray, dst: np.ndarray, n_dst: int,
                    rows: np.ndarray) -> np.ndarray:
    """Per destination, the mean of its in-neighbours' ``rows`` (0 where
    it has none), by one sparse product."""
    adj = sp.csr_matrix((np.ones(len(src), np.float32), (dst, src)),
                        shape=(n_dst, len(rows)))
    deg = np.maximum(np.diff(adj.indptr), 1).astype(np.float32)
    return (np.asarray(adj @ rows) / deg[:, None]).astype(np.float32)


def make_typed_graph(traffic: dict) -> Graph:
    """The ``"typed"`` generator.  The traffic file states:

    * ``node_types``: ``[{"name", "published"}]``; the target type has
      ``2**scale`` nodes and every other type its published ratio to it;
    * ``relations``: ``[{"src", "name", "dst", ...}]`` in message
      direction (edges point toward the target type).  A drawn relation
      has ``edges_per_src`` draws a source node, endpoints with
      ``src_skew`` and ``dst_skew`` (:func:`skewed_ids`), and
      ``undirected`` to add each edge's reverse to the same relation; a
      reverse relation names the relation it reverses in ``reverse_of``.
      Self-loops and duplicates are dropped within each relation;
    * ``target``: the type that alone has labels, the split and
      class-centred features (label propagation over ``label_relation``,
      a target-to-target relation, as the R-MAT generator does);
    * ``features_by_mean``: ``[[type, relation], ...]``, in order: that
      type's rows are the mean of its in-neighbours' rows over the
      relation, whose source type has its rows already.

    Fused ids put the node types contiguously in declaration order."""
    rng = np.random.default_rng(int(traffic["data_seed"]))
    target = traffic["target"]
    types = [t["name"] for t in traffic["node_types"]]
    base = {t["name"]: float(t["published"]) for t in traffic["node_types"]}
    n_target = 1 << int(traffic["scale"])
    count = {t: max(1, int(round(n_target * base[t] / base[target])))
             for t in types}
    count[target] = n_target
    offset = dict(zip(types, np.cumsum([0] + [count[t] for t in types])))
    n = int(sum(count.values()))

    local, schema = {}, []
    for rel in traffic["relations"]:
        s_t, name, d_t = rel["src"], rel["name"], rel["dst"]
        schema.append((s_t, name, d_t))
        if "reverse_of" in rel:
            u, v = local[rel["reverse_of"]]
            local[name] = _simple(v, u, count[d_t], s_t == d_t)
            continue
        m = int(round(float(rel["edges_per_src"]) * count[s_t]))
        u = skewed_ids(m, count[s_t], float(rel.get("src_skew", 0)), rng)
        v = skewed_ids(m, count[d_t], float(rel.get("dst_skew", 0)), rng)
        if rel.get("undirected"):
            u, v = np.concatenate([u, v]), np.concatenate([v, u])
        local[name] = _simple(u, v, count[d_t], s_t == d_t)

    d = int(traffic["feat_dim"])
    feats = np.zeros((n, d), np.float32)
    labels = np.full(n, -1, np.int64)
    split = np.zeros(n, np.int8)
    by_name = {r: (s_t, d_t) for s_t, r, d_t in schema}
    if by_name[traffic["label_relation"]] != (target, target):
        raise SystemExit("label_relation must join the target type to "
                         "itself")
    lo = offset[target]
    u, v = local[traffic["label_relation"]]
    labels[lo:lo + n_target], feats[lo:lo + n_target] = labels_and_features(
        u, v, n_target, int(traffic["num_classes"]), d, rng)
    split[lo:lo + n_target] = split_mask(n_target,
                                         float(traffic["train_frac"]),
                                         float(traffic["val_frac"]), rng)
    for t, rel in traffic["features_by_mean"]:
        s_t, d_t = by_name[rel]
        if d_t != t:
            raise SystemExit(f"features of {t!r} over {rel!r}, which ends "
                             f"at {d_t!r}")
        u, v = local[rel]
        src_rows = feats[offset[s_t]:offset[s_t] + count[s_t]]
        feats[offset[t]:offset[t] + count[t]] = _neighbour_mean(
            u, v, count[t], src_rows)

    src = np.concatenate([local[r][0] + offset[s_t] for s_t, r, _ in schema])
    dst = np.concatenate([local[r][1] + offset[d_t] for _, r, d_t in schema])
    etypes = np.repeat(np.arange(len(schema), dtype=np.int32),
                       [len(local[r][0]) for _, r, _ in schema])
    ntypes = np.repeat(np.arange(len(types), dtype=np.int32),
                       [count[t] for t in types])
    return Graph(src=src, dst=dst, feats=feats, labels=labels, split=split,
                 num_classes=int(traffic["num_classes"]), ntypes=ntypes,
                 etypes=etypes, schema={"ntypes": tuple(types),
                                        "relations": tuple(schema)})


def as_dataset(g: Graph, name: str):
    """The program's input object for the same graph: on a typed graph
    with its ``HeteroSchema``, which switches the program's typed path
    on."""
    from repro.graph.csr import from_edges
    from repro.graph.datasets import GraphDataset
    if g.schema is None:
        return GraphDataset(name=name, graph=from_edges(g.src, g.dst,
                                                        g.num_nodes),
                            feats=g.feats, labels=g.labels,
                            split_mask=g.split, num_classes=g.num_classes)
    from repro.graph.hetero import HeteroSchema
    schema = HeteroSchema(ntypes=tuple(g.schema["ntypes"]),
                          canonical_etypes=tuple(g.schema["relations"]))
    graph = from_edges(g.src, g.dst, g.num_nodes, etypes=g.etypes,
                       ntypes=g.ntypes, num_etypes=schema.num_etypes,
                       num_ntypes=schema.num_ntypes)
    return GraphDataset(name=name, graph=graph, feats=g.feats,
                        labels=g.labels, split_mask=g.split,
                        num_classes=g.num_classes, schema=schema)


# ---- the model and the job -------------------------------------------------

def _fanouts(config: dict, g: Graph) -> list:
    """The configuration's fanouts, input layer first: ints on an untyped
    graph, ``{relation name: fanout}`` maps on a typed one."""
    typed = g.schema is not None
    out = []
    for f in config["fanouts"]:
        if isinstance(f, dict) != typed:
            raise SystemExit(f"fanout {f!r}: a typed graph takes "
                             "{relation: fanout} maps, an untyped one ints")
        if typed:
            unknown = set(f) - set(g.relations)
            if unknown:
                raise SystemExit(f"fanouts name relations the traffic has "
                                 f"not: {sorted(unknown)}")
            out.append({r: int(v) for r, v in f.items()})
        else:
            out.append(int(f))
    return out


def model_config(config: dict, g: Graph):
    """The program's ``GNNConfig``: the configuration file's sizes, with
    ``in_dim`` and ``num_classes`` from the graph, and on a typed graph
    ``num_rels`` from its relations."""
    from repro.models.gnn import GNNConfig
    typed = {} if g.schema is None else {"num_rels": len(g.relations)}
    return GNNConfig(arch=config["arch"], in_dim=int(g.feats.shape[1]),
                     hidden_dim=int(config["hidden_dim"]),
                     num_classes=g.num_classes,
                     fanouts=_fanouts(config, g),
                     batch_size=int(config["batch_size"]),
                     num_heads=int(config.get("num_heads", 1)), **typed)


def arch_config(config: dict, g: Graph) -> dict:
    """The configuration as the reference model reads it: the file as
    written, and on a typed graph ``num_rels``, the count of the
    traffic's relations, which sizes the relation weights."""
    if g.schema is None:
        return config
    return dict(config, num_rels=len(g.relations))


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


def _fanout_total(f) -> int:
    return sum(int(v) for v in f.values()) if isinstance(f, dict) else int(f)


def capacities(batch_size: int, fanouts: list) -> list:
    """Static ``(cap_dst, cap_edge, cap_src)`` per layer, input layer
    first: ``cap_dst`` of the last layer is the batch, each layer's
    ``cap_edge = cap_dst * fanout`` and ``cap_src = cap_dst + cap_edge``,
    which is the next layer inward's ``cap_dst``.  A typed layer's
    fanout is a ``{relation: fanout}`` map, and counts as its sum."""
    out = []
    cap_dst = int(batch_size)
    for f in reversed(list(fanouts)):
        cap_edge = cap_dst * _fanout_total(f)
        out.append((cap_dst, cap_edge, cap_dst + cap_edge))
        cap_dst += cap_edge
    return out[::-1]


def relation_slots(batch_size: int, fanouts: list, relations: list) -> list:
    """Per layer, input layer first, the static slot offsets of each
    relation on a typed block's edge axis: a tuple of ``R + 1`` offsets,
    relation ``r`` (in ``relations``' order) owning
    ``[offsets[r], offsets[r + 1])``, ``cap_dst * fanout_r`` slots."""
    out = []
    for (cap_dst, _, _), f in zip(capacities(batch_size, fanouts), fanouts):
        sizes = [cap_dst * int(f.get(r, 0)) for r in relations]
        out.append(tuple(int(x) for x in np.cumsum([0] + sizes)))
    return out
