"""Packed one-shot device staging (DESIGN.md §9, ISSUE 6).

The contract under test: ``pack -> single device transfer -> unpack`` is
*byte-identical* to per-array ``jax.device_put`` of the same tree — same
dtypes (jax's x64 canonicalization applied host-side), same shapes, same
bytes — with ``None`` leaves restored, the arena laid out in 32-bit words
so every dtype segment is word- and itemsize-aligned, and the spec/offset
table a pure function of the batch's (path, shape, dtype) set.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.pack import (PackSpec, PackedBatch, device_stage,
                                flatten_tree, pack, unflatten_tree, unpack)

ops = importlib.import_module("repro.kernels.pack.ops")

RNG = np.random.default_rng(11)


def _batch_tree(seed=0):
    """A MiniBatch-shaped tree covering every staged dtype family,
    including an x64 leaf (canonicalized) and None leaves."""
    rng = np.random.default_rng(seed)
    return dict(
        input_feats=rng.standard_normal((16, 32)).astype(np.float32),
        seeds=rng.integers(0, 100, 16).astype(np.int64),
        seed_mask=rng.integers(0, 2, 16).astype(bool),
        labels=None,
        blocks=[dict(edge_src=rng.integers(0, 50, 40).astype(np.int32),
                     edge_dst=rng.integers(0, 16, 40).astype(np.int32),
                     edge_mask=rng.integers(0, 2, 40).astype(bool),
                     edge_types=None),
                dict(edge_src=rng.integers(0, 50, 80).astype(np.int32),
                     edge_dst=rng.integers(0, 50, 80).astype(np.int32),
                     edge_mask=rng.integers(0, 2, 80).astype(bool),
                     edge_types=rng.integers(0, 4, 80).astype(np.int64))])


def _flat_bytes(tree):
    flat, nones = flatten_tree(jax.tree.map(np.asarray, tree))
    return ({k: (v.dtype, v.shape, v.tobytes()) for k, v in flat.items()},
            nones)


def test_roundtrip_byte_identical_to_per_array():
    tree = _batch_tree()
    staged = device_stage(tree, packed=True)
    assert isinstance(staged, PackedBatch)
    per_array = device_stage(tree, packed=False)
    assert _flat_bytes(staged.unpack()) == _flat_bytes(per_array)
    # None leaves resurface in place
    out = staged.unpack()
    assert out["labels"] is None
    assert out["blocks"][0]["edge_types"] is None
    # the staged form is ONE device buffer: the uint32 arena
    assert staged.buffers.dtype == jnp.uint32
    assert staged.buffers.shape == (staged.spec.arena_words,)
    assert 0 <= 4 * staged.spec.arena_words - staged.total_bytes() \
        < 4 * staged.spec.num_buffers


def test_unpack_cached_and_getitem():
    staged = device_stage(_batch_tree(1), packed=True)
    assert staged.unpack() is staged.unpack()
    np.testing.assert_array_equal(staged["seeds"],
                                  staged.unpack()["seeds"])


def test_arena_segments_itemsize_aligned_and_disjoint():
    spec, arena = pack(_batch_tree(2))
    assert arena.dtype == np.uint32 and arena.size == spec.arena_words
    end = 0
    seen_itemsize = None
    payload = 0
    for dt, boff, n in spec.arena_layout:
        item = np.dtype(dt).itemsize
        assert boff % item == 0, f"segment {dt} misaligned at byte {boff}"
        # each segment starts on the first word after the previous one
        assert boff == -(-end // 4) * 4, "segments must tile the words"
        end = boff + n * item
        payload += n * item
        # descending-itemsize order is what makes alignment automatic
        assert seen_itemsize is None or item <= seen_itemsize
        seen_itemsize = item
    assert -(-end // 4) == spec.arena_words
    assert payload == spec.total_bytes()


def test_spec_is_pure_function_of_fields_and_cached():
    t = _batch_tree(3)
    spec_a, _ = pack(t)
    # same shapes/dtypes under a different dict insertion order -> the
    # SAME cached spec object (the lru_cache key is the sorted field set)
    reordered = dict(reversed(list(t.items())))
    spec_b, _ = pack(reordered)
    assert spec_a is spec_b
    # a different shape is a different spec
    t2 = _batch_tree(3)
    t2["input_feats"] = t2["input_feats"][:, :16].copy()
    spec_c, _ = pack(t2)
    assert spec_c is not spec_a


def test_x64_leaves_canonicalized_like_jax():
    tree = dict(a=np.arange(7, dtype=np.int64),
                b=np.linspace(0, 1, 5).astype(np.float64),
                c=np.arange(3, dtype=np.uint64))
    out = device_stage(tree, packed=True).unpack()
    ref = jax.tree.map(jax.device_put, tree)
    for k in tree:
        assert out[k].dtype == ref[k].dtype, k
        assert np.asarray(out[k]).tobytes() == np.asarray(ref[k]).tobytes()


def test_unpack_traceable_inside_outer_jit():
    """The donation path: unpack_flat must fuse into a jitted consumer."""
    tree = dict(x=RNG.standard_normal((8, 4)).astype(np.float32),
                n=RNG.integers(0, 9, 8).astype(np.int32))
    spec, arena = pack(tree)

    @jax.jit
    def consume(buf):
        flat = ops.unpack_flat(spec, buf)
        return flat["x"].sum(axis=1) + flat["n"].astype(np.float32)

    got = consume(jax.device_put(arena))
    want = tree["x"].sum(axis=1) + tree["n"].astype(np.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_flatten_unflatten_inverse():
    tree = _batch_tree(4)
    flat, nones = flatten_tree(tree)
    rebuilt = unflatten_tree(flat, nones)
    assert _flat_bytes(rebuilt) == _flat_bytes(tree)
    assert isinstance(rebuilt["blocks"], list) and len(rebuilt["blocks"]) == 2


_DTYPES = [np.float32, np.int32, np.int64, np.bool_, np.uint8]


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_pack_roundtrip_property(data):
    """Random trees: any mix of dtypes/shapes/Nones round-trips to the
    exact per-array staging bytes."""
    seed = data.draw(st.integers(0, 1000))
    rng = np.random.default_rng(seed)
    n_fields = data.draw(st.integers(1, 8))
    tree = {}
    for i in range(n_fields):
        kind = data.draw(st.integers(0, len(_DTYPES)))
        if kind == len(_DTYPES):
            tree[f"f{i}"] = None
            continue
        nd = data.draw(st.integers(0, 2))
        shape = tuple(data.draw(st.integers(1, 9)) for _ in range(nd))
        dt = _DTYPES[kind]
        if dt is np.bool_:
            arr = rng.integers(0, 2, shape).astype(bool)
        elif np.issubdtype(dt, np.floating):
            arr = rng.standard_normal(shape).astype(dt)
        else:
            arr = rng.integers(0, 100, shape).astype(dt)
        tree[f"f{i}"] = arr
    if all(v is None for v in tree.values()):
        tree["anchor"] = np.zeros(1, np.float32)
    staged = device_stage(tree, packed=True)
    per_array = device_stage(tree, packed=False)
    assert _flat_bytes(staged.unpack()) == _flat_bytes(per_array)
    spec = staged.spec
    assert spec.total_bytes() == sum(
        n * np.dtype(dt).itemsize for dt, _, n in spec.arena_layout)


def test_scalar_and_zero_dim_leaves():
    tree = dict(s=np.float32(2.5), z=np.array(7, dtype=np.int32))
    out = device_stage(tree, packed=True).unpack()
    assert out["s"].shape == () and float(out["s"]) == 2.5
    assert out["z"].shape == () and int(out["z"]) == 7
