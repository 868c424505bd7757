"""Packed one-shot device staging (DESIGN.md §9).

The device-prefetch stage used to ship every mini-batch as ~10 independent
``jax.device_put`` calls of small arrays — one per field, four more per MFG
block — so the stage was dominated by per-transfer overhead, not bandwidth.
This module packs the whole batch into **one contiguous host arena** of
32-bit words (one contiguous segment per dtype — at most four: f32 / i64 /
i32 / bool — each starting on a word) and issues a **single one-buffer**
``jax.device_put``; the per-field views are
recovered *on device* by a jitted unpack whose byte offsets are
compile-time constants (the padded-MFG capacity contract of DESIGN.md §2
makes every shape static, so the same :class:`PackSpec` — and the same
compiled unpack — is reused for every batch of a run).

Value contract: staging through ``pack -> device_put -> unpack`` is
*byte-identical* to per-array ``device_put`` of the same tree.  Both paths
apply exactly jax's canonicalization casts (with x64 disabled an int64
array lands as int32 either way, applied here on the host while filling
the packed arena), and unpacking is pure static slicing + reshape +
bitcast — ``lax.bitcast_convert_type`` from the arena's words back to
each dtype is bit-exact by definition, and the bool segment is recovered
with ``!= 0`` (exact: NumPy bool storage is 0/1 bytes).  No arithmetic
touches the payload.

Why words and not bytes: a 32-bit segment is then an elementwise bitcast
of a slice of the arena.  Unpacking it from bytes needs a ``(n, 4)``
uint8 intermediate, which the TPU lays out with its minor dimension
padded to 128 lanes — 32x the segment (a 1.7 GB feature block asked for
56 GB).  Only sub-word segments (bool masks) still take that path, at
their own, small, size.

Layout: leaves are keyed by their "/"-joined tree path (lists by index,
e.g. ``blocks/0/edge_src``), sorted by key within each dtype segment so
the offset table is a pure function of the spec; dtype segments are laid
out in descending-itemsize order, each padded to whole words, so every
segment's byte offset is a multiple of both 4 and its itemsize.  ``None``
leaves are recorded in the spec and resurface as ``None`` on unpack (a
label-less epoch keeps its ``labels=None`` slot).
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SEP = "/"
WORD = 4          # the arena is uint32 words

# dtypes jax silently canonicalizes when x64 is disabled; applied on the
# host while filling the buffer so packed == per-array staging bit-for-bit
_CANON = {np.dtype(np.int64): np.dtype(np.int32),
          np.dtype(np.uint64): np.dtype(np.uint32),
          np.dtype(np.float64): np.dtype(np.float32)}


def _canon_dtype(dt: np.dtype) -> np.dtype:
    if jax.config.jax_enable_x64:
        return dt
    return _CANON.get(dt, dt)


@dataclasses.dataclass(frozen=True)
class PackSpec:
    """Static description of one packed batch: per-field (path, shape,
    dtype) plus the paths of ``None`` leaves.  Hashable — it is the cache
    key for the compiled unpack program."""

    fields: Tuple[Tuple[str, Tuple[int, ...], str], ...]
    none_paths: Tuple[str, ...] = ()

    @functools.cached_property
    def layout(self) -> Tuple[Tuple[str, Tuple[int, ...], str, int, int], ...]:
        """(path, shape, dtype, offset, size) per field; offsets count
        elements within that dtype's 1-D buffer, in sorted-path order."""
        cursor: Dict[str, int] = {}
        out = []
        for path, shape, dt in sorted(self.fields):
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            off = cursor.get(dt, 0)
            out.append((path, shape, dt, off, size))
            cursor[dt] = off + size
        return tuple(out)

    @functools.cached_property
    def buffer_sizes(self) -> Dict[str, int]:
        sizes: Dict[str, int] = {}
        for _, _, dt, off, size in self.layout:
            sizes[dt] = off + size
        return sizes

    @property
    def num_buffers(self) -> int:
        return len(self.buffer_sizes)

    @functools.cached_property
    def arena_layout(self) -> Tuple[Tuple[str, int, int], ...]:
        """(dtype, byte_offset, num_elements) per dtype segment of the
        arena, in descending-itemsize order — each segment starts on a
        32-bit word, so its offset is a multiple of 4 and of its itemsize
        (itemsizes are powers of two)."""
        segs = sorted(self.buffer_sizes.items(),
                      key=lambda kv: (-np.dtype(kv[0]).itemsize, kv[0]))
        out, off = [], 0
        for dt, n in segs:
            out.append((dt, off, n))
            off += -(-n * np.dtype(dt).itemsize // WORD) * WORD
        return tuple(out)

    def total_bytes(self) -> int:
        """Payload bytes (the arena adds < 4 bytes of padding per dtype)."""
        return sum(n * np.dtype(dt).itemsize
                   for dt, n in self.buffer_sizes.items())

    @property
    def arena_words(self) -> int:
        dt, off, n = self.arena_layout[-1]
        return -(-(off + n * np.dtype(dt).itemsize) // WORD)


def flatten_tree(tree: Any) -> Tuple[Dict[str, np.ndarray], Tuple[str, ...]]:
    """Nested dict/list/tuple batch -> ({path: array}, none_paths)."""
    flat: Dict[str, np.ndarray] = {}
    nones = []

    def walk(prefix: str, node: Any) -> None:
        if node is None:
            nones.append(prefix)
        elif isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}{SEP}{k}" if prefix else str(k), v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(f"{prefix}{SEP}{i}" if prefix else str(i), v)
        else:
            flat[prefix] = np.asarray(node)

    walk("", tree)
    return flat, tuple(sorted(nones))


def unflatten_tree(flat: Dict[str, Any], none_paths: Tuple[str, ...] = ()
                   ) -> Any:
    """Inverse of :func:`flatten_tree`: "/"-paths back to nested
    dicts/lists (a node whose keys are all decimal becomes a list)."""
    root: Dict[str, Any] = {}
    for path in list(flat) + list(none_paths):
        parts = path.split(SEP)
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = None if path in none_paths else flat[path]

    def rebuild(node: Any) -> Any:
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            return [rebuild(node[str(i)]) for i in range(len(node))]
        return {k: rebuild(v) for k, v in node.items()}

    return rebuild(root)


@functools.lru_cache(maxsize=256)
def _spec_cache(fields, none_paths) -> PackSpec:
    # padded-MFG shapes are static across a run (DESIGN.md §2), so every
    # batch hits the same spec — the layout/offset table is computed once
    return PackSpec(fields, none_paths)


def pack(tree: Any) -> Tuple[PackSpec, np.ndarray]:
    """Flatten a host batch into ONE contiguous uint32 arena (one segment
    per dtype, fields at static offsets within their segment)."""
    flat, none_paths = flatten_tree(tree)
    fields = []
    for path, arr in flat.items():
        dt = _canon_dtype(arr.dtype)
        fields.append((path, tuple(arr.shape), dt.str))
    spec = _spec_cache(tuple(sorted(fields)), none_paths)
    arena = np.zeros(spec.arena_words, dtype=np.uint32)
    views = _segment_views(spec, arena)
    for path, shape, dt, off, size in spec.layout:
        # ravel + canonicalization cast in one copy into the arena
        np.copyto(views[dt][off:off + size].reshape(shape), flat[path],
                  casting="unsafe")
    return spec, arena


def _segment_views(spec: PackSpec, arena: np.ndarray
                   ) -> Dict[str, np.ndarray]:
    raw = arena.view(np.uint8)
    return {dt: raw[boff:boff + n * np.dtype(dt).itemsize].view(dt)
            for dt, boff, n in spec.arena_layout}


def stacked_spec(batches: List[Any]) -> PackSpec:
    """The spec of ``pack(np.stack(batches))`` without stacking them:
    each field of ``batches[0]`` becomes ``(path, (T,) + shape, canon
    dtype)``.  Every batch must have the same paths, shapes, dtypes and
    ``None`` paths (§2's capacity contract); a mismatch raises."""
    flat0, none_paths = flatten_tree(batches[0])
    want = {p: (a.shape, a.dtype) for p, a in flat0.items()}
    for t, b in enumerate(batches[1:], 1):
        flat, nones = flatten_tree(b)
        got = {p: (a.shape, a.dtype) for p, a in flat.items()}
        if got != want or nones != none_paths:
            raise ValueError(
                f"batch {t} is not laid out as batch 0: fields "
                f"{sorted(got.items())} and None paths {nones} against "
                f"{sorted(want.items())} and {none_paths}")
    fields = tuple(sorted(
        (p, (len(batches),) + a.shape, _canon_dtype(a.dtype).str)
        for p, a in flat0.items()))
    return _spec_cache(fields, none_paths)


def pack_into(spec: PackSpec, arena: np.ndarray, batches: List[Any]
              ) -> np.ndarray:
    """Write the T batches of ``spec = stacked_spec(batches)`` straight
    into ``arena``, batch ``t`` into row ``t`` of every field, with the
    canonicalization cast of :func:`pack`: ONE copy of the step's bytes.

    ``arena`` is a uint32 buffer of ``spec.arena_words`` that was zeroed
    when it was allocated: every byte a field owns is written here and
    the padding words at the ends of segments are never touched, so the
    arena equals ``pack(np.stack(batches))[1]`` byte for byte."""
    if arena.dtype != np.uint32 or arena.shape != (spec.arena_words,):
        raise ValueError(f"arena {arena.dtype}{arena.shape} does not hold "
                         f"spec of {spec.arena_words} words")
    views = _segment_views(spec, arena)
    flats = [flatten_tree(b)[0] for b in batches]
    for path, shape, dt, off, size in spec.layout:
        n = size // shape[0]
        for t, flat in enumerate(flats):
            np.copyto(views[dt][off + t * n:off + (t + 1) * n]
                      .reshape(shape[1:]), flat[path], casting="unsafe")
    return arena


@functools.lru_cache(maxsize=None)
def _unpack_fn(spec: PackSpec):
    """Compiled device-side unpack for one spec: static byte slices +
    bitcast back to each dtype + per-field reshape (offsets are python
    ints at trace time -> compile-time constants; every step bit-exact)."""
    segs = {}
    for dt, boff, n in spec.arena_layout:
        segs[dt] = (boff, n, np.dtype(dt))

    def unpack_flat(arena: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        bufs = {}
        for dt, (boff, n, nd) in segs.items():
            w0 = boff // WORD
            w1 = w0 + -(-n * nd.itemsize // WORD)
            words = arena[w0:w1]
            if nd.itemsize > WORD:           # (n, 2) words per element
                words = words.reshape(n, nd.itemsize // WORD)
            elif nd.itemsize < WORD:         # WORD // itemsize per word
                words = jax.lax.bitcast_convert_type(
                    words, np.dtype(f"u{nd.itemsize}")).reshape(-1)[:n]
            if nd == np.dtype(bool):
                bufs[dt] = words != 0        # exact: bool bytes are 0/1
            else:
                bufs[dt] = jax.lax.bitcast_convert_type(words, nd)
        out = {}
        for path, shape, dt, off, size in spec.layout:
            out[path] = bufs[dt][off:off + size].reshape(shape)
        return out

    return jax.jit(unpack_flat)


def unpack_flat(spec: PackSpec, arena: jnp.ndarray
                ) -> Dict[str, jnp.ndarray]:
    """Device arena -> {path: device array}.  Also traceable inside an
    outer jit (the donation path fuses it into the train step)."""
    return _unpack_fn(spec)(arena)


def unpack(spec: PackSpec, arena: jnp.ndarray) -> Any:
    """Device arena -> the original nested tree (``None`` leaves
    restored), every leaf a view into the packed device arena."""
    return unflatten_tree(unpack_flat(spec, arena), spec.none_paths)


class PackedBatch:
    """One staged mini-batch: the spec + its device-resident uint32 arena.

    ``unpack()`` recovers the nested device tree (cached — slicing a
    resident buffer is cheap but not free); ``buffers`` is the single
    arena array, the donation unit a jitted step can consume with
    ``donate_argnums`` (DESIGN.md §9: donate only on non-CPU backends —
    the CPU runtime warns and ignores).
    """

    __slots__ = ("spec", "buffers", "_tree")

    def __init__(self, spec: PackSpec, buffers: jnp.ndarray):
        self.spec = spec
        self.buffers = buffers
        self._tree = None

    def unpack(self) -> Any:
        if self._tree is None:
            self._tree = unpack(self.spec, self.buffers)
        return self._tree

    def __getitem__(self, key: str) -> Any:
        return self.unpack()[key]

    def total_bytes(self) -> int:
        return self.spec.total_bytes()


@functools.lru_cache(maxsize=1)
def _cpu_backend() -> bool:
    return jax.default_backend() == "cpu"


def stage_arena(arena: np.ndarray) -> jnp.ndarray:
    """The host arena -> one device buffer."""
    # On the CPU backend the dlpack import is the cheapest ingest path
    # (same bytes, lower dispatch overhead than device_put).  On an
    # accelerator it would land the buffer on the HOST device, so there
    # we keep device_put (one H2D transfer of the whole arena).
    if _cpu_backend():
        return jnp.from_dlpack(arena)
    return jax.device_put(arena)


def _zeroed_words(n: int, align: int = 64) -> np.ndarray:
    """``n`` zeroed uint32 words starting on an ``align``-byte boundary:
    the CPU runtime then aliases every arena in place of copying it, so
    whether the hazard that :meth:`StackedArena.stage` guards against
    arises is not left to the allocator."""
    raw = np.zeros(n * WORD + align, dtype=np.uint8)
    off = -raw.ctypes.data % align
    return raw[off:off + n * WORD].view(np.uint32)


class StackedArena:
    """The host arena of a training step, kept from step to step
    (DESIGN.md §9): the T trainers' batches are written straight into it,
    stacked on a leading axis (:func:`pack_into`), in place of
    ``pack(np.stack(batches))``, which copies the step's bytes twice,
    each time into fresh pages.

    One arena is kept, for the spec of the last step; a new spec
    allocates a new one (``allocs`` counts them).  Before the arena is
    written again the last buffer staged from it must be ready, as its
    transfer reads the arena.  The staged buffer is a copy on every
    backend: the CPU runtime would alias the host arena (dlpack, or
    ``device_put`` of an aligned buffer), and step k's device arrays
    would then change under step k+1's fill."""

    def __init__(self):
        self.spec: PackSpec | None = None
        self.arena: np.ndarray | None = None
        self.allocs = 0
        # weak reference to the last staged buffer (held, it would keep
        # the step's device input alive through the next step's staging)
        self._staged = lambda: None

    def fill(self, batches: List[Any]) -> PackSpec:
        """Pack the step's T host batches into the arena; returns the
        stacked spec."""
        spec = stacked_spec(batches)
        if spec != self.spec:
            self.spec, self.arena = spec, _zeroed_words(spec.arena_words)
            self.allocs += 1
        elif (last := self._staged()) is not None:
            # train_epoch has synced on the step that read it, so this
            # returns at once; a dead buffer was read by such a step too
            last.block_until_ready()
        pack_into(spec, self.arena, batches)
        return spec

    def stage(self) -> PackedBatch:
        """The filled arena -> one device buffer that shares no memory
        with it."""
        if _cpu_backend():
            buf = jnp.array(self.arena, copy=True)
        else:
            buf = jax.device_put(self.arena)
        self._staged = weakref.ref(buf)
        return PackedBatch(self.spec, buf)


def device_stage(tree: Any, packed: bool = True):
    """The shared device-prefetch helper (both mini-batch pipelines and
    the LM token stream stage through here).

    ``packed=True``: pack -> ONE single-buffer transfer of the uint32
    arena -> :class:`PackedBatch`.  ``packed=False``: the legacy
    per-array path — one ``device_put`` per leaf, ``None`` leaves passed
    through — kept as the ablation baseline the benchmarks compare
    against.
    """
    if not packed:
        return jax.tree.map(jax.device_put, tree)
    spec, arena = pack(tree)
    return PackedBatch(spec, stage_arena(arena))
