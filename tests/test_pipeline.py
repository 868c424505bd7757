import time

import numpy as np
import pytest

from repro.core.pipeline import AsyncPipeline, Stage
from repro.core.pipeline.minibatch import MinibatchPipeline
from repro.core.kvstore import (DistKVStore, FaultInjector, NetworkModel,
                                PartitionPolicy, Transport,
                                TransientRPCError)
from repro.core.partition import hierarchical_partition, split_training_set
from repro.core.sampler import DistributedSampler
from repro.graph import get_dataset


def test_async_pipeline_preserves_order_and_results():
    stages = [Stage("double", lambda x: x * 2, depth=3),
              Stage("inc", lambda x: x + 1, depth=2)]
    out = list(AsyncPipeline(range(50), stages))
    assert out == [x * 2 + 1 for x in range(50)]


def test_async_pipeline_sync_mode_identical():
    stages = [Stage("sq", lambda x: x * x, depth=2)]
    a = list(AsyncPipeline(range(20), stages, sync=True))
    b = list(AsyncPipeline(range(20), stages, sync=False))
    assert a == b


def test_async_pipeline_overlaps_stage_latency():
    def slow(x):
        time.sleep(0.01)
        return x
    stages = [Stage("s1", slow, depth=4), Stage("s2", slow, depth=4)]
    t0 = time.perf_counter()
    consumed = 0
    for _ in AsyncPipeline(range(20), stages):
        time.sleep(0.01)   # consumer work
        consumed += 1
    dt = time.perf_counter() - t0
    assert consumed == 20
    # 3 overlapped 10ms stages for 20 items: ~0.2s+ramp, not 0.6s serial
    assert dt < 0.45, dt


def test_async_pipeline_error_propagates():
    def boom(x):
        if x == 3:
            raise ValueError("boom")
        return x
    with pytest.raises(ValueError):
        list(AsyncPipeline(range(10), [Stage("b", boom, depth=2)]))


def test_stop_joins_threads_blocked_on_full_queues():
    # Depth-1 queues + an abandoned consumer: every stage ends up blocked
    # on put() into a full queue. stop() must wake and join them all.
    stages = [Stage("a", lambda x: x, depth=1), Stage("b", lambda x: x, depth=1)]
    p = AsyncPipeline(range(100000), stages)
    it = iter(p)
    next(it)                    # start threads, then abandon the iterator
    time.sleep(0.1)             # queues fill; producers block on put()
    threads = list(p._threads)
    p.stop(timeout=5.0)
    assert all(not t.is_alive() for t in threads)


def test_stop_does_not_leak_thread_stuck_mid_stage_fn():
    # A worker still inside fn() when stop()'s join window expires must
    # still exit afterwards (its input get() re-checks the stop flag).
    import threading
    started = threading.Event()

    def slow(x):
        started.set()
        time.sleep(0.5)
        return x

    p = AsyncPipeline(range(10), [Stage("slow", slow, depth=1)])
    it = iter(p)
    next(it)
    started.clear()
    started.wait(timeout=2.0)          # a later item is mid-fn
    threads = list(p._threads)
    p.stop(timeout=0.05)               # expires while fn still sleeping
    time.sleep(1.0)                    # fn returns; worker must then exit
    assert all(not t.is_alive() for t in threads)


def test_stop_idempotent_and_safe_after_drain():
    p = AsyncPipeline(range(5), [Stage("x", lambda x: x, depth=2)])
    assert list(p) == list(range(5))
    p.stop()
    p.stop()


def test_stage_stats_recorded():
    p = AsyncPipeline(range(10), [Stage("w", lambda x: x, depth=2)])
    list(p)
    assert p.stats_report()["w"]["items"] == 10


def _consume(pipe, consumer_s=0.0):
    out = []
    for x in pipe:
        time.sleep(consumer_s)
        out.append(x)
    return out


def test_consumer_wait_counts_a_slow_stage():
    def slow(x):
        time.sleep(0.05)
        return x
    p = AsyncPipeline(range(8), [Stage("slow", slow, depth=2)])
    assert _consume(p) == list(range(8))
    c = p.stats_report()["consumer"]
    assert c["items"] == 8
    assert c["wait_in_s"] >= 0.8 * 8 * 0.05, c


def test_consumer_wait_near_zero_behind_a_fast_stage():
    p = AsyncPipeline(range(8), [Stage("fast", lambda x: x, depth=2)])
    assert _consume(p, consumer_s=0.05) == list(range(8))
    c = p.stats_report()["consumer"]
    assert c["items"] == 8
    # the consumer's own 0.4 s dwarfs its wait: every item was ready
    assert c["wait_in_s"] < 0.2 * 8 * 0.05, c


def test_consumer_wait_in_sync_mode_counts_whole_items():
    def slow(x):
        time.sleep(0.01)
        return x
    p = AsyncPipeline(range(10), [Stage("a", slow), Stage("b", slow)],
                      sync=True)
    assert _consume(p) == list(range(10))
    c = p.stats_report()["consumer"]
    assert c["items"] == 10
    assert c["wait_in_s"] >= 0.8 * 10 * 0.02, c


def test_pool_preserves_order_under_out_of_order_completion():
    # Worker pool stress: per-item delays force completions far out of
    # order (item 0 is the slowest of each wave); the reassembly buffer
    # must still emit strictly in sequence.
    def jitter(x):
        time.sleep(0.012 - 0.003 * (x % 4))
        return x * 10
    stages = [Stage("pool", jitter, depth=2, workers=4),
              Stage("tail", lambda x: x + 1, depth=2)]
    p = AsyncPipeline(range(40), stages)
    assert list(p) == [x * 10 + 1 for x in range(40)]
    rep = p.stats_report()
    assert rep["pool"]["items"] == 40 and rep["pool"]["workers"] == 4
    assert rep["tail"]["items"] == 40 and rep["tail"]["workers"] == 1


@pytest.mark.slow
def test_pool_overlaps_item_latency():
    # 4 workers on a 10ms stage must beat the serial 0.3s floor clearly.
    # Wall-clock on a busy 1-core host is noisy: best of 2 runs, like
    # test_minibatch_pipeline_async_faster_than_sync.
    def slow(x):
        time.sleep(0.01)
        return x

    def run():
        t0 = time.perf_counter()
        out = list(AsyncPipeline(range(30),
                                 [Stage("s", slow, depth=4, workers=4)]))
        assert out == list(range(30))
        return time.perf_counter() - t0

    dt = min(run() for _ in range(2))
    assert dt < 0.25, dt   # serial would be >= 0.3s


def test_pool_reorder_buffer_bounded():
    # One very slow batch must not let the siblings race ahead without
    # bound (the ordering window): while item 0 blocks, at most
    # workers+depth items may complete, no matter how deep the source is.
    import threading
    release = threading.Event()

    def fn(x):
        if x == 0:
            release.wait(timeout=10)
        return x

    p = AsyncPipeline(range(5000), [Stage("s", fn, depth=2, workers=4)])
    p.start()
    time.sleep(0.5)                  # pool runs while item 0 is stuck
    done_ahead = p.stats["s"].items
    release.set()
    assert list(p) == list(range(5000))
    assert done_ahead <= 4 + 2, done_ahead   # the workers+depth window
    p.stop()


def test_pool_error_stops_sibling_workers():
    # After one worker errors, siblings must stop invoking fn (their side
    # effects would pollute transport accounting) instead of burning
    # through the rest of an unbounded schedule.
    import threading
    calls = [0]
    lock = threading.Lock()

    def boom(x):
        with lock:
            calls[0] += 1
        if x == 5:
            raise ValueError("boom")
        time.sleep(0.002)
        return x

    p = AsyncPipeline(range(100000), [Stage("b", boom, depth=2, workers=4)])
    with pytest.raises(ValueError):
        list(p)
    time.sleep(0.3)                  # grace for siblings to notice
    with lock:
        seen = calls[0]
    time.sleep(0.3)
    with lock:
        assert calls[0] <= seen + 4, "workers kept running fn after error"
    p.stop()


def test_pool_error_propagates():
    def boom(x):
        if x == 7:
            raise ValueError("boom")
        time.sleep(0.002)
        return x
    p = AsyncPipeline(range(50), [Stage("b", boom, depth=2, workers=4)])
    with pytest.raises(ValueError):
        list(p)
    p.stop()


def test_pool_stop_joins_threads():
    stages = [Stage("a", lambda x: x, depth=1, workers=3),
              Stage("b", lambda x: x, depth=1)]
    p = AsyncPipeline(range(100000), stages)
    it = iter(p)
    next(it)
    time.sleep(0.1)             # queues fill; workers block on put()
    threads = list(p._threads)
    p.stop(timeout=5.0)
    assert all(not t.is_alive() for t in threads)


def test_pool_sync_mode_ignores_workers():
    stages = [Stage("sq", lambda x: x * x, depth=2, workers=8)]
    assert (list(AsyncPipeline(range(20), stages, sync=True))
            == [x * x for x in range(20)])


@pytest.fixture(scope="module")
def world():
    ds = get_dataset("product-sim", scale=11)
    hp = hierarchical_partition(ds.graph, 2, 1, split_mask=ds.split_mask,
                                seed=0)
    book = hp.book
    feats_new = ds.feats[book.new2old_node]
    labels_new = ds.labels[book.new2old_node]
    tp = Transport(NetworkModel(sleep=True, latency_s=2e-3,
                                bandwidth_Bps=1e9))
    store = DistKVStore({"node": PartitionPolicy("node", book.node_offsets)},
                        transport=tp)
    store.init_data("feat", feats_new.shape[1:], np.float32, "node",
                    full_array=feats_new)
    train_new = book.old2new_node[ds.train_nids]
    seeds = split_training_set(hp, train_new)[0]
    return ds, hp, store, tp, seeds, labels_new


def _run(world, sync, non_stop, epochs=3, consume_s=0.008):
    ds, hp, store, tp, seeds, labels_new = world
    sampler = DistributedSampler(hp.book, hp.partitions, [10, 5], 32,
                                 machine=0, transport=tp, seed=0)
    pipe = MinibatchPipeline(sampler, store.client(0), "feat", seeds,
                             labels=labels_new[seeds], sync=sync,
                             non_stop=non_stop, to_device=False, seed=1)
    t0 = time.perf_counter()
    got = []
    for e in range(epochs):
        for mb in pipe.epoch(e):
            time.sleep(consume_s)   # stands in for the jitted train step
            got.append(mb)
    dt = time.perf_counter() - t0
    pipe.stop()
    return dt, got


def test_minibatch_pipeline_same_count_all_modes(world):
    _, a = _run(world, True, False)
    _, b = _run(world, False, False)
    _, c = _run(world, False, True)
    assert len(a) == len(b) == len(c) > 0
    # every minibatch has features attached by the CPU prefetch stage
    assert all(m.input_feats is not None for m in a + b + c)


@pytest.mark.slow
def test_minibatch_pipeline_async_faster_than_sync(world):
    # Wall-clock comparison on a busy 1-core host is noisy: take the best
    # of 2 runs per mode; async must beat the serial loop. If a
    # scheduling hiccup inverts it, retry once with two more runs per
    # mode and a 5% noise allowance — min-of-4 makes the comparison
    # robust, and a genuine overlap regression (async degenerating to
    # serial plus thread overhead) loses by far more than 5% across all
    # runs, so the widened margin only forgives timer jitter, not the
    # property under test.
    t_sync = min(_run(world, True, False)[0] for _ in range(2))
    t_async = min(_run(world, False, True)[0] for _ in range(2))
    if t_async >= t_sync:
        t_sync = min([t_sync] + [_run(world, True, False)[0]
                                 for _ in range(2)])
        t_async = min([t_async] + [_run(world, False, True)[0]
                                   for _ in range(2)])
        assert t_async < t_sync * 1.05, (t_async, t_sync)
    else:
        assert t_async < t_sync


def test_consumer_counter_survives_non_stop_epochs(world):
    ds, hp, store, tp, seeds, labels_new = world
    sampler = DistributedSampler(hp.book, hp.partitions, [5], 32,
                                 machine=0, transport=tp, seed=0)
    pipe = MinibatchPipeline(sampler, store.client(0), "feat", seeds,
                             labels=labels_new[seeds], non_stop=True,
                             to_device=False, seed=1)
    reports = []
    for e in range(2):
        assert len(list(pipe.epoch(e))) == pipe.batches_per_epoch
        reports.append(pipe.stats_report()["consumer"])
    pipe.stop()
    n = pipe.batches_per_epoch
    assert [r["items"] for r in reports] == [n, 2 * n]
    assert 0.0 < reports[0]["wait_in_s"] < reports[1]["wait_in_s"]


def test_pipeline_feature_correctness(world):
    ds, hp, store, tp, seeds, labels_new = world
    feats_new = ds.feats[hp.book.new2old_node]
    sampler = DistributedSampler(hp.book, hp.partitions, [5], 16,
                                 machine=0, seed=0)
    pipe = MinibatchPipeline(sampler, store.client(0), "feat", seeds,
                             labels=labels_new[seeds], sync=True,
                             non_stop=False, to_device=False)
    for mb in pipe.epoch(0):
        assert np.allclose(mb.input_feats, feats_new[mb.input_gids])
        break


# ---- injected mid-stream stage failures (DESIGN.md §10) -------------------

def _fault_world():
    """A private world per test: these tests poison the shared transport
    with a fault injector, so they must never touch the module fixture."""
    ds = get_dataset("product-sim", scale=10)
    hp = hierarchical_partition(ds.graph, 2, 1, split_mask=ds.split_mask,
                                seed=0)
    book = hp.book
    feats_new = ds.feats[book.new2old_node]
    labels_new = ds.labels[book.new2old_node]
    tp = Transport(NetworkModel(sleep=True, latency_s=2e-3,
                                bandwidth_Bps=1e9))
    store = DistKVStore({"node": PartitionPolicy("node", book.node_offsets)},
                        transport=tp)
    store.init_data("feat", feats_new.shape[1:], np.float32, "node",
                    full_array=feats_new)
    train_new = book.old2new_node[ds.train_nids]
    seeds = split_training_set(hp, train_new)[0]
    return hp, store, tp, seeds, labels_new


@pytest.mark.parametrize("workers", [2, 4])
def test_pool_worker_fault_drains_cleanly(workers):
    """An injected fault inside a pool worker mid-way through a NON-STOP
    schedule must surface to the consumer, stop the sibling workers, and
    leave zero pipeline threads after ``stop()`` — a crashed sampling
    worker must never wedge or leak the trainer's pipeline."""
    import threading
    hp, store, tp, seeds, labels_new = _fault_world()
    # ops=("data",): fault the sampler-dispatch RPCs, i.e. the SAMPLE
    # stage's own traffic (that path is deliberately not retried — only
    # pull/push are, so the fault surfaces as a worker crash)
    tp.fault_injector = FaultInjector(seed=2, rpc_failure_rate=1.0,
                                      ops=("data",),)
    sampler = DistributedSampler(hp.book, hp.partitions, [10, 5], 32,
                                 machine=0, transport=tp, seed=0)
    pipe = MinibatchPipeline(sampler, store.client(0), "feat", seeds,
                             labels=labels_new[seeds], non_stop=True,
                             to_device=False, seed=1,
                             sample_workers=workers)
    with pytest.raises(TransientRPCError):
        for _ in pipe.epoch(0):
            pass
    pipe.stop()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("minibatch")]
    assert not leaked, f"pipeline threads leaked after fault: {leaked}"


@pytest.mark.parametrize("workers", [2, 4])
def test_pool_worker_fault_stops_siblings(workers):
    """After one sampling worker crashes, siblings must stop issuing
    dispatch RPCs (their side effects would pollute transport accounting)
    instead of burning through the rest of the non-stop schedule."""
    hp, store, tp, seeds, labels_new = _fault_world()
    tp.fault_injector = FaultInjector(seed=2, rpc_failure_rate=1.0,
                                      ops=("data",))
    sampler = DistributedSampler(hp.book, hp.partitions, [10, 5], 32,
                                 machine=0, transport=tp, seed=0)
    pipe = MinibatchPipeline(sampler, store.client(0), "feat", seeds,
                             labels=labels_new[seeds], non_stop=True,
                             to_device=False, seed=1,
                             sample_workers=workers)
    with pytest.raises(TransientRPCError):
        for _ in pipe.epoch(0):
            pass
    time.sleep(0.3)                   # grace for siblings to notice
    n_then = tp.rpc_failures
    time.sleep(0.3)
    # each worker may finish the item it already held, nothing more
    assert tp.rpc_failures <= n_then + workers, \
        "sampling workers kept issuing RPCs after a sibling's fault"
    pipe.stop()


def test_pipeline_fault_free_run_unaffected_by_armed_injector():
    """An attached injector with a zero rate (or out-of-scope ops) is
    inert: batch bytes and transport accounting match a run with no
    injector at all — the golden hashes cannot move."""
    outs = []
    for inj in (None, FaultInjector(seed=9, rpc_failure_rate=0.0),
                FaultInjector(seed=9, rpc_failure_rate=1.0,
                              ops=("never",))):
        hp, store, tp, seeds, labels_new = _fault_world()
        tp.fault_injector = inj
        sampler = DistributedSampler(hp.book, hp.partitions, [5, 3], 16,
                                     machine=0, transport=tp, seed=0)
        pipe = MinibatchPipeline(sampler, store.client(0), "feat", seeds,
                                 labels=labels_new[seeds], non_stop=False,
                                 to_device=False, seed=1)
        got = [(mb.input_gids.tobytes(), mb.input_feats.tobytes())
               for mb in pipe.epoch(0)]
        pipe.stop()
        assert tp.rpc_failures == 0 and tp.rpc_retries == 0
        outs.append(got)
    assert outs[0] == outs[1] == outs[2]
