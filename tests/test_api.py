"""The repro.api public surface (ISSUE 5 acceptance, DESIGN.md §8):

  * loader protocol — ``NodeDataLoader`` / ``EdgeDataLoader`` yield
    DGL-style triples whose batches are byte-for-byte what driving the
    pipelines directly produces (async and sync, homogeneous and typed,
    cache on and off — the same constructions test_sample_workers.py
    hashes), re-iteration advances epochs, ``len(loader)`` matches the
    schedule;
  * teardown — breaking out mid-epoch leaks no pool/feeder threads and
    does not poison the next epoch: after ``close()`` a full epoch is
    byte-identical to an uninterrupted run, and the raw pipeline refuses
    to mislabel an abandoned stream;
  * ``DistGraph`` — ``ndata`` pulls equal direct ``KVClient.pull`` /
    ``pull_typed``, ``node_split`` is disjoint and covers the training
    ids, ``edge_split`` equalizes owned ranges, ``DistTensor`` enforces
    read-only features and version-tracked writes;
  * surface hygiene — ``repro`` / ``repro.api`` export the documented
    names, old import paths warn, the API boundary check catches direct
    pipeline construction.
"""
import hashlib
import itertools
import threading

import numpy as np
import pytest

from repro.api import (DistEmbedding, DistGraph, DistTensor, EdgeBatch,
                       EdgeDataLoader, NodeBatch, NodeDataLoader)
from repro.core.kvstore import CacheConfig
from repro.core.pipeline import EdgeMinibatchPipeline, MinibatchPipeline
from repro.core.sampler import DistributedSampler, EdgeBatchSampler
from repro.graph import get_dataset

FANOUTS_TYPED = {"cites": 5, "writes": 3, "rev_writes": 2, "employs": 2}


@pytest.fixture(scope="module")
def homo_g():
    ds = get_dataset("product-sim", scale=10)
    return DistGraph(ds, num_machines=2, trainers_per_machine=1, seed=0)


@pytest.fixture(scope="module")
def hetero_g():
    ds = get_dataset("mag-hetero", scale=10)
    return DistGraph(ds, num_machines=2, trainers_per_machine=1,
                     hetero=True, seed=0)


def _hash_node_batches(mbs):
    h = hashlib.sha256()
    n = 0
    for mb in mbs:
        for b in mb.blocks:
            for arr in (b.src_gids, b.edge_src, b.edge_dst, b.edge_mask,
                        b.edge_types):
                h.update(np.ascontiguousarray(arr).tobytes())
        h.update(mb.seeds.tobytes())
        h.update(mb.seed_mask.tobytes())
        h.update(np.int64([mb.epoch, mb.batch_index]).tobytes())
        h.update(np.ascontiguousarray(mb.input_feats).tobytes())
        n += 1
    return h.hexdigest(), n


def _hash_edge_batches(embs):
    h = hashlib.sha256()
    n = 0
    for emb in embs:
        for b in emb.blocks:
            for arr in (b.src_gids, b.edge_src, b.edge_dst, b.edge_mask,
                        b.edge_types):
                h.update(np.ascontiguousarray(arr).tobytes())
        for arr in (emb.seeds, emb.pos_eids, emb.pos_src, emb.pos_dst,
                    emb.neg_dst, emb.neg_v, emb.edge_etypes, emb.pair_mask):
            h.update(np.ascontiguousarray(arr).tobytes())
        h.update(np.ascontiguousarray(emb.input_feats).tobytes())
        n += 1
    return h.hexdigest(), n


def _epoch_stream(loader_or_pipe, epochs=2):
    for e in range(epochs):
        yield from loader_or_pipe.epoch(e)


# ---------------------------------------------------------------------------
# byte-identity: loaders vs the pipelines they wrap
# ---------------------------------------------------------------------------

def test_node_loader_matches_pipeline_bytes(homo_g):
    g = homo_g
    seeds = g.train_nids[:256]
    labels = g.labels[seeds]

    def pipe_hash(sync):
        s = DistributedSampler(g.book, g.partitions, [10, 5], 32,
                               machine=0, seed=5)
        pipe = MinibatchPipeline(s, g.store.client(0), "feat", seeds,
                                 labels=labels, sync=sync, non_stop=False,
                                 to_device=False, seed=6)
        out = _hash_node_batches(_epoch_stream(pipe))
        pipe.stop()
        return out

    def loader_hash(sync):
        ld = NodeDataLoader(g, seeds, [10, 5], batch_size=32, labels=labels,
                            sync=sync, non_stop=False, seed=6,
                            sampler_seed=5)
        out = _hash_node_batches(
            b.minibatch for b in _epoch_stream(ld))
        ld.close()
        return out

    h_ref, n_ref = pipe_hash(sync=True)
    assert n_ref == 2 * (len(seeds) // 32) > 0
    for sync in (True, False):
        h, n = loader_hash(sync)
        assert n == n_ref
        assert h == h_ref, f"loader (sync={sync}) changed the node stream"


def test_typed_node_loader_matches_pipeline_and_cache_invariant(hetero_g):
    g = hetero_g
    seeds = g.train_nids[:96]
    labels = g.labels[seeds]
    fanouts = [dict(FANOUTS_TYPED)] * 2

    def pipe_hash():
        s = DistributedSampler(g.book, g.partitions, fanouts, 16, machine=0,
                               seed=15, schema=g.schema,
                               ntype_of_node=g.typed.ntype_of_node)
        pipe = MinibatchPipeline(s, g.store.client(0), "feat", seeds,
                                 labels=labels, sync=False, non_stop=False,
                                 to_device=False, seed=16, typed=g.typed)
        out = _hash_node_batches(_epoch_stream(pipe))
        pipe.stop()
        return out

    def loader_hash(cache):
        ld = NodeDataLoader(g, seeds, fanouts, batch_size=16, labels=labels,
                            sync=False, non_stop=False, seed=16,
                            sampler_seed=15, cache=cache)
        out = _hash_node_batches(b.minibatch for b in _epoch_stream(ld))
        ld.close()
        return out

    h_ref, n_ref = pipe_hash()
    assert n_ref > 0
    assert loader_hash(None) == (h_ref, n_ref)
    cache = g.feature_cache(CacheConfig.from_mb(64))
    h_on, n_on = loader_hash(cache)
    assert (h_on, n_on) == (h_ref, n_ref), "cache changed the typed stream"
    assert cache.stats()["hits"] > 0, "cache never hit — test proves nothing"


def test_edge_loader_matches_pipeline_bytes(homo_g):
    g = homo_g
    owned = g.trainer_view(0).edge_split()[:512]
    B, K = 32, 3

    def pipe_hash():
        node_bs = EdgeBatchSampler.required_node_batch(B, K)
        s = DistributedSampler(g.book, g.partitions, [5, 5], node_bs,
                               machine=0, seed=25)
        e_src, e_dst = g.edge_endpoints()
        es = EdgeBatchSampler(s, e_src, e_dst, owned, B, K, seed=26)
        pipe = EdgeMinibatchPipeline(es, g.store.client(0), "feat",
                                     sync=False, non_stop=False,
                                     to_device=False, seed=27)
        out = _hash_edge_batches(_epoch_stream(pipe))
        pipe.stop()
        return out

    def loader_hash(cache=None):
        ld = EdgeDataLoader(g, owned, [5, 5], batch_size=B, num_negs=K,
                            sync=False, non_stop=False, seed=27,
                            sampler_seed=25, edge_seed=26, cache=cache)
        out = _hash_edge_batches(b.minibatch for b in _epoch_stream(ld))
        ld.close()
        return out

    h_ref, n_ref = pipe_hash()
    assert n_ref == 2 * (len(owned) // B)
    assert loader_hash() == (h_ref, n_ref)
    cache = g.feature_cache(CacheConfig.from_mb(64))
    assert loader_hash(cache) == (h_ref, n_ref), \
        "cache changed the edge stream"
    assert cache.stats()["hits"] > 0


# ---------------------------------------------------------------------------
# golden byte-identity: packed staging + fused kernels vs the per-array /
# unfused path (ISSUE 6 acceptance)
# ---------------------------------------------------------------------------

def _device_tree_bytes(dev) -> dict:
    """{path: (dtype, shape, bytes)} of a staged device tree (PackedBatch
    or per-array dict alike)."""
    from repro.kernels.pack import PackedBatch, flatten_tree
    tree = dev.unpack() if isinstance(dev, PackedBatch) else dev
    flat, nones = flatten_tree(
        __import__("jax").tree.map(np.asarray, tree))
    out = {k: (str(v.dtype), v.shape, v.tobytes()) for k, v in flat.items()}
    out["__none__"] = nones
    return out


def _staged_stream(loader_cls, g, ids, fanouts, packed, **kw):
    ld = loader_cls(g, ids, fanouts, device_prefetch=True,
                    packed_staging=packed, sync=True, non_stop=False, **kw)
    out = [_device_tree_bytes(b.device) for b in ld.epoch(0)]
    ld.close()
    return out


@pytest.mark.parametrize("gfix", ["homo_g", "hetero_g"])
def test_packed_staging_byte_identity_node(gfix, request):
    g = request.getfixturevalue(gfix)
    seeds = g.train_nids[:64]
    fanouts = [dict(FANOUTS_TYPED)] * 2 if g.hetero else [5, 5]
    kw = dict(batch_size=16, labels=g.labels[seeds], seed=31,
              sampler_seed=32)
    packed = _staged_stream(NodeDataLoader, g, seeds, fanouts, True, **kw)
    per_arr = _staged_stream(NodeDataLoader, g, seeds, fanouts, False, **kw)
    assert len(packed) == len(per_arr) > 0
    assert packed == per_arr, "packed staging changed the device bytes"


@pytest.mark.parametrize("gfix", ["homo_g", "hetero_g"])
def test_packed_staging_byte_identity_edge(gfix, request):
    g = request.getfixturevalue(gfix)
    owned = g.edge_split()[:64]
    fanouts = [dict(FANOUTS_TYPED)] * 2 if g.hetero else [5, 5]
    kw = dict(batch_size=8, num_negs=3, seed=33, sampler_seed=34,
              edge_seed=35)
    packed = _staged_stream(EdgeDataLoader, g, owned, fanouts, True, **kw)
    per_arr = _staged_stream(EdgeDataLoader, g, owned, fanouts, False, **kw)
    assert len(packed) == len(per_arr) > 0
    assert packed == per_arr, "packed staging changed the device bytes"


def test_model_input_packed_contract(homo_g):
    from repro.kernels.pack import PackedBatch
    g = homo_g
    seeds = g.train_nids[:32]
    with NodeDataLoader(g, seeds, [5, 5], batch_size=16,
                        labels=g.labels[seeds], device_prefetch=True,
                        packed_staging=True, sync=True, non_stop=False,
                        seed=41) as ld:
        b = next(iter(ld))
        staged = b.model_input(packed=True)
        assert isinstance(staged, PackedBatch)
        # the unpacked model_input is a view of the SAME staged batch
        mi = b.model_input()
        assert set(mi) == set(NodeBatch._model_keys)
        assert np.array_equal(np.asarray(mi["input_feats"]),
                              np.asarray(staged["input_feats"]))
    # host-side loaders refuse the packed form
    with NodeDataLoader(g, seeds, [5, 5], batch_size=16,
                        labels=g.labels[seeds], seed=41) as ld:
        with pytest.raises(ValueError, match="packed"):
            next(iter(ld)).model_input(packed=True)


def _train_golden(ds, cfg, job_kw, epochs):
    import jax
    from repro.api import DistGNNTrainer, TrainJobConfig
    tr = DistGNNTrainer(ds, cfg, TrainJobConfig(
        num_machines=2, trainers_per_machine=1, seed=5, **job_kw))
    losses = [tr.train_epoch(e)["loss"] for e in range(epochs)]
    params = jax.tree_util.tree_leaves(tr.params)
    blob = b"".join(np.asarray(p).tobytes() for p in params)
    tr.stop()
    return losses, blob


@pytest.mark.parametrize("task,arch,dataset,scale,epochs", [
    ("node_classification", "graphsage", "product-sim", 11, 2),
    ("node_classification", "rgcn", "mag-sim", 13, 2),
    # LP schedules EVERY owned edge per epoch — smaller graphs keep the
    # golden runs short without weakening the bitwise pin
    ("link_prediction", "graphsage", "product-sim", 9, 1),
    ("link_prediction", "rgcn", "mag-sim", 10, 1),
])
def test_trainer_packed_fused_golden_bytes(task, arch, dataset, scale,
                                           epochs):
    """The acceptance pin: packed staging + the fused-kernel dispatch
    (``impl`` explicit) train to BIT-IDENTICAL losses and parameter bytes
    vs the per-array / pre-fusion path, on node+edge × homo+typed."""
    from repro.graph import get_dataset
    from repro.models.gnn import GNNConfig
    ds = get_dataset(dataset, scale=scale)
    cfg = GNNConfig(arch=arch, in_dim=ds.feats.shape[1], hidden_dim=16,
                    num_classes=(16 if task == "link_prediction"
                                 else ds.num_classes),
                    fanouts=[5, 5], batch_size=32,
                    num_rels=ds.graph.num_etypes)
    kw = dict(task=task)
    if task == "link_prediction":
        kw["num_negs"] = 3
    ref = _train_golden(ds, cfg, dict(packed_staging=False, impl="ref",
                                      **kw), epochs)
    new = _train_golden(ds, cfg, dict(packed_staging=True, impl="auto",
                                      **kw), epochs)
    assert new[0] == ref[0], f"losses diverged: {new[0]} vs {ref[0]}"
    assert new[1] == ref[1], "parameter bytes diverged"


# ---------------------------------------------------------------------------
# loader protocol: DGL triples, len, epoch advancement
# ---------------------------------------------------------------------------

def test_node_loader_yields_dgl_triples(homo_g):
    g = homo_g
    seeds = g.train_nids[:128]
    with NodeDataLoader(g, seeds, [5, 5], batch_size=32,
                        labels=g.labels[seeds], seed=3) as ld:
        assert len(ld) == len(seeds) // 32
        batch = next(iter(ld))
        assert isinstance(batch, NodeBatch)
        input_nodes, out_seeds, blocks = batch
        mb = batch.minibatch
        assert input_nodes is mb.input_gids
        assert out_seeds is mb.seeds
        assert blocks is mb.blocks
        mi = batch.model_input()
        assert set(mi) == {"input_feats", "labels", "seed_mask", "blocks"}
        assert np.array_equal(mi["input_feats"], mb.input_feats)
        assert len(mi["blocks"]) == 2


def test_edge_loader_yields_dgl_triples(homo_g):
    g = homo_g
    owned = g.edge_split()[:128]
    with EdgeDataLoader(g, owned, [5, 5], batch_size=16, num_negs=3,
                        seed=4) as ld:
        batch = next(iter(ld))
        assert isinstance(batch, EdgeBatch)
        input_nodes, pair_graph, blocks = batch
        emb = batch.minibatch
        assert input_nodes is emb.input_gids
        assert blocks is emb.blocks
        # the pair graph is the scoring-head view of the same batch
        assert np.array_equal(pair_graph.pos_u, emb.pos_u)
        assert np.array_equal(pair_graph.neg_v, emb.neg_v)
        assert np.array_equal(pair_graph.pair_mask, emb.pair_mask)
        assert pair_graph.batch_edges == 16 and pair_graph.num_negs == 3
        mi = batch.model_input()
        assert set(mi) == {"input_feats", "seed_mask", "pos_u", "pos_v",
                           "neg_v", "pair_mask", "edge_etypes", "blocks"}


def test_reiteration_advances_epochs_nonstop(homo_g):
    g = homo_g
    seeds = g.train_nids[:128]
    ld = NodeDataLoader(g, seeds, [5], batch_size=32,
                        labels=g.labels[seeds], seed=7, non_stop=True)
    first = list(ld)                       # epoch 0, clean StopIteration
    second = list(ld)                      # epoch 1 on the same pipeline
    assert len(first) == len(second) == len(ld) > 0
    assert all(b.epoch == 0 for b in first)
    assert all(b.epoch == 1 for b in second)
    # explicit epoch driving obeys the §7 consecutive-epoch contract
    with pytest.raises(ValueError, match="consecutive"):
        next(ld.epoch(9))
    third = list(ld.epoch(2))
    assert all(b.epoch == 2 for b in third)
    ld.close()
    # close() rewinds: iteration restarts from the abandoned epoch counter
    again = list(ld.epoch(0))
    assert all(b.epoch == 0 for b in again)
    ld.close()


# ---------------------------------------------------------------------------
# teardown on partial consumption
# ---------------------------------------------------------------------------

def _pipeline_threads():
    return [t for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("minibatch")]


def test_partial_consumption_no_leak_and_byte_identical_epoch(homo_g):
    g = homo_g
    seeds = g.train_nids[:256]
    labels = g.labels[seeds]
    kw = dict(batch_size=32, labels=labels, seed=11, sampler_seed=12,
              non_stop=True, sample_workers=2)

    # reference: an uninterrupted epoch 0 from a fresh loader
    ref_ld = NodeDataLoader(g, seeds, [5, 5], **kw)
    h_ref, n_ref = _hash_node_batches(b.minibatch for b in iter(ref_ld))
    ref_ld.close()
    assert not _pipeline_threads(), "reference loader leaked threads"

    ld = NodeDataLoader(g, seeds, [5, 5], **kw)
    taken = list(itertools.islice(ld, 2))       # break out mid-epoch
    assert len(taken) == 2 < n_ref
    assert _pipeline_threads(), "non-stop pipeline should be live"
    ld.close()                                   # drains + joins + rewinds
    assert not _pipeline_threads(), \
        "close() left pool/feeder threads alive after partial consumption"
    # the abandoned epoch did not count: the next iteration re-serves
    # epoch 0, byte-identical to the uninterrupted run
    h2, n2 = _hash_node_batches(b.minibatch for b in iter(ld))
    assert (h2, n2) == (h_ref, n_ref)
    ld.close()
    assert not _pipeline_threads()


def test_iter_after_abandonment_auto_recovers(homo_g):
    g = homo_g
    seeds = g.train_nids[:256]
    ld = NodeDataLoader(g, seeds, [5], batch_size=32,
                        labels=g.labels[seeds], seed=13, non_stop=True)
    h_ref, n_ref = _hash_node_batches(b.minibatch for b in iter(ld))
    ld.close()
    # abandon mid-epoch, then iterate WITHOUT an explicit close(): the
    # loader rewinds itself and re-serves the same epoch byte-identically
    list(itertools.islice(ld, 1))
    h2, n2 = _hash_node_batches(b.minibatch for b in iter(ld))
    assert (h2, n2) == (h_ref, n_ref)
    ld.close()
    assert not _pipeline_threads()


def test_drain_to_epoch_boundary_keeps_pipeline_alive(homo_g):
    """The trainer's contract for unequal per-trainer batch counts (typed
    LP): draining an epoch iterator to its boundary finishes the epoch
    cleanly — no teardown, no rebuild, next epoch advances on the same
    live pipeline."""
    g = homo_g
    seeds = g.train_nids[:256]
    ld = NodeDataLoader(g, seeds, [5], batch_size=32,
                        labels=g.labels[seeds], seed=17, non_stop=True)
    it = ld.epoch(0)
    for _ in range(len(ld) - 1):          # consume all but the last batch
        next(it)
    for _ in it:                          # drain to the epoch boundary
        pass
    live = ld.pipeline._pipe
    assert live is not None
    nxt = list(ld.epoch(1))
    assert all(b.epoch == 1 for b in nxt)
    assert ld.pipeline._pipe is live, \
        "draining to the boundary must not tear the pipeline down"
    ld.close()


def test_pipeline_refuses_mislabeled_epoch_after_abandonment(homo_g):
    g = homo_g
    seeds = g.train_nids[:256]
    s = DistributedSampler(g.book, g.partitions, [5], 32, machine=0, seed=45)
    pipe = MinibatchPipeline(s, g.store.client(0), "feat", seeds,
                             sync=False, non_stop=True, to_device=False,
                             seed=46)
    it = pipe.epoch(0)
    next(it)                                  # abandon epoch 0 mid-stream
    with pytest.raises(ValueError, match="mid-epoch"):
        next(pipe.epoch(1))
    pipe.stop()                               # stop() rewinds the contract
    assert all(mb.epoch == 0 for mb in pipe.epoch(0))
    pipe.stop()


# ---------------------------------------------------------------------------
# DistGraph: ndata / DistTensor / splits
# ---------------------------------------------------------------------------

def test_ndata_pulls_equal_kvclient(homo_g):
    g = homo_g
    ids = np.linspace(0, g.num_nodes() - 1, 37, dtype=np.int64)
    feat = g.ndata["feat"]
    assert isinstance(feat, DistTensor)
    assert feat.shape == (g.num_nodes(), g.ds.feats.shape[1])
    assert len(feat) == g.num_nodes()
    client = g.store.client(0)
    assert np.array_equal(feat[ids], client.pull("feat", ids))
    assert np.array_equal(g.ndata["label"][ids],
                          client.pull("label", ids))
    assert set(g.ndata.keys()) == {"feat", "label"}
    assert "feat" in g.ndata and "nope" not in g.ndata
    with pytest.raises(KeyError):
        g.ndata["nope"]
    # features are read-only through the façade
    with pytest.raises(TypeError, match="read-only"):
        feat[ids[:2]] = np.zeros((2, feat.shape[1]), np.float32)


def test_ndata_typed_pulls_equal_pull_typed(hetero_g):
    g = hetero_g
    ids = np.linspace(0, g.num_nodes() - 1, 29, dtype=np.int64)
    client = g.store.client(0)
    fused = g.ndata["feat"]          # fused-ID view over the typed family
    assert np.array_equal(fused[ids],
                          client.pull_typed("feat", ids, g.typed))
    # per-ntype tensors are first-class keys too (type-local ids)
    nt0 = g.schema.ntypes[0]
    tl = np.arange(5, dtype=np.int64)
    assert np.array_equal(g.ndata[f"feat:{nt0}"][tl],
                          client.pull(f"feat:{nt0}", tl))


def test_dist_embedding_writable_through_ndata(homo_g):
    g = homo_g
    emb = DistEmbedding(g.store, "api_emb", g.num_nodes(), 8, "node",
                        seed=3)
    t = g.ndata["api_emb"]
    assert t.writable, "version-tracked embedding tables accept writes"
    ids = np.array([1, 5, 9], dtype=np.int64)
    before = t[ids]
    t[ids] = before + 1.0
    assert np.array_equal(t[ids], before + 1.0)
    # the embedding's own pull sees the same rows
    assert np.array_equal(emb.pull(g.client, ids), before + 1.0)


def test_node_split_disjoint_and_covers(homo_g):
    g = homo_g
    train = g.train_nids
    splits = g.node_splits(train)
    assert len(splits) == g.num_trainers
    sizes = {len(s) for s in splits}
    assert len(sizes) == 1, "sync SGD needs equal per-trainer seed counts"
    flat = np.concatenate(splits)
    assert len(flat) == len(np.unique(flat)), "splits overlap"
    assert np.isin(flat, train).all()
    # equal counts drop at most num_trainers-1 tail seeds
    assert len(flat) >= len(train) - (g.num_trainers - 1)
    for r in range(g.num_trainers):
        assert np.array_equal(g.trainer_view(r).node_split(train), splits[r])


def test_edge_split_equalized_owned_ranges(homo_g):
    g = homo_g
    splits = g.edge_splits()
    assert len(splits) == g.num_trainers
    assert len({len(s) for s in splits}) == 1, "pools not equalized"
    offs = g.book.edge_offsets
    T = g.trainers_per_machine
    for r, eids in enumerate(splits):
        m = r // T
        assert (eids >= offs[m]).all() and (eids < offs[m + 1]).all(), \
            f"trainer {r} schedules edges outside machine {m}'s owned range"
    flat = np.concatenate(splits)
    assert len(flat) == len(np.unique(flat)), "edge pools overlap"
    assert np.array_equal(g.trainer_view(1).edge_split(), splits[1])


def test_eval_loader_matches_direct_sampler(homo_g):
    g = homo_g
    nids = g.val_nids[:96]
    bs = 32
    ld = NodeDataLoader(g, nids, [5, 5], batch_size=bs,
                        labels=g.labels[nids], mode="eval", sampler_seed=99)
    got = list(ld)
    s = DistributedSampler(g.book, g.partitions, [5, 5], bs, machine=0,
                           seed=99)
    client = g.store.client(0)
    assert len(got) == len(nids) // bs
    for b, batch in enumerate(got):
        chunk = nids[b * bs:(b + 1) * bs]
        mb = s.sample(chunk, labels=g.labels[chunk], batch_index=b)
        assert np.array_equal(batch.seeds, mb.seeds)
        assert np.array_equal(batch.labels, mb.labels)
        assert np.array_equal(batch.input_feats,
                              client.pull("feat", mb.input_gids))
    # eval loaders spin up no pipeline threads and are re-iterable
    assert ld.pipeline is None
    assert len(list(ld)) == len(got)
    ld.close()


def test_loader_stats_report(homo_g):
    g = homo_g
    seeds = g.train_nids[:128]
    cache = g.feature_cache(CacheConfig.from_mb(8))
    ld = NodeDataLoader(g, seeds, [5, 5], batch_size=32,
                        labels=g.labels[seeds], seed=21, cache=cache,
                        non_stop=False)
    list(ld)
    rep = ld.stats_report()
    ld.close()
    assert rep["batches_per_epoch"] == len(ld)
    assert set(rep["stages"]) == {"sample", "cpu_prefetch",
                                  "device_prefetch", "consumer"}
    assert rep["stages"]["consumer"]["items"] == len(ld)
    assert rep["stages"]["sample"]["items"] == len(ld)
    assert rep["sampler"]["batches"] == len(ld)
    assert rep["sampler"]["coalescing_factor"] == 1.0   # untyped
    assert 0.0 <= rep["cache"]["hit_rate"] <= 1.0


# ---------------------------------------------------------------------------
# surface hygiene
# ---------------------------------------------------------------------------

def test_public_surface_exports():
    import repro
    import repro.api as api
    want = {"DistGraph", "DistTensor", "DistEmbedding", "NodeDataLoader",
            "EdgeDataLoader", "DistGNNTrainer", "TrainJobConfig"}
    assert want <= set(api.__all__)
    assert want <= set(repro.__all__)
    for name in want:
        assert getattr(repro, name) is getattr(api, name)
    # the lazy trainer re-export resolves to the real implementation
    from repro.training.trainer import DistGNNTrainer as impl
    assert api.DistGNNTrainer is impl
    with pytest.raises(AttributeError):
        api.no_such_name


def test_deprecated_training_import_warns():
    with pytest.warns(DeprecationWarning, match="repro.api"):
        from repro.training import DistGNNTrainer  # noqa: F401
    with pytest.warns(DeprecationWarning, match="repro.api"):
        from repro.training import TrainJobConfig  # noqa: F401
    # the implementation module itself stays warning-free
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        from repro.training.trainer import TrainJobConfig  # noqa: F401,F811


def test_api_boundary_checker_catches_planted_violation(tmp_path):
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    try:
        import check_docs
    finally:
        sys.path.pop(0)
    bad = tmp_path / "src" / "repro" / "training"
    bad.mkdir(parents=True)
    (bad / "rogue.py").write_text(
        "p = MinibatchPipeline(s, c, 'feat', seeds)\n", encoding="utf-8")
    errors = check_docs.check_api_boundary(tmp_path)
    assert errors and "rogue.py" in errors[0]
    # the class definition site and api/ itself stay exempt
    ok = tmp_path / "src" / "repro" / "api"
    ok.mkdir(parents=True)
    (ok / "loader.py").write_text(
        "p = EdgeMinibatchPipeline(es, c, 'feat')\n", encoding="utf-8")
    assert check_docs.check_api_boundary(tmp_path) == errors
    # the real tree is clean
    assert check_docs.check_api_boundary(
        Path(__file__).resolve().parent.parent) == []
