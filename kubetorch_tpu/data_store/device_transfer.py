"""Device-array transfer through the data store — host-staged.

The reference moves GPU tensors between workloads zero-copy via CUDA IPC +
NCCL broadcast groups (``data_store/gpu_transfer.py:124``,
``pod_data_server.py``). TPU has no CUDA-IPC analogue (SURVEY.md §7
hard-part 3), so this path is **host-staged by design**: arrays are fetched
to host, packed into one contiguous buffer (header = msgpack tree spec +
shapes/dtypes, mirroring the reference's packed single-buffer mode), moved
through the store (delta/P2P as for any blob), and placed back onto devices —
optionally resharded onto a different mesh than they were saved from, which
the reference cannot do at all.

This is what RL weight-sync uses (trainer publishes, inference workers
fetch — the async-GRPO pattern); steady-state checkpointing should prefer
:mod:`kubetorch_tpu.training.checkpoint` (Orbax, per-shard parallel IO).
"""

from __future__ import annotations

import contextvars
import functools
import io
import os
import queue
import threading
import time
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple

import msgpack
import numpy as np

from kubetorch_tpu.data_store import codec as codec_mod
from kubetorch_tpu.data_store import commands as store
from kubetorch_tpu.data_store.types import BLOB_DELTA_SUFFIX
from kubetorch_tpu.exceptions import DataStoreError
from kubetorch_tpu.observability import tracing

_MAGIC = b"KTARRV1\x00"

# Decomposition of the most recent get_arrays restore in this process —
# read by bench_dataplane and mirrored into the Prometheus counters
# (observability.prometheus.record_restore). Plain dict, overwritten per
# restore: the bench and the metrics push both want "the last one".
_LAST_RESTORE: Dict[str, float] = {}

# Ditto for the most recent put_arrays publish: wire vs raw bytes, encode
# time, and the delta-skip decomposition.
_LAST_PUBLISH: Dict[str, float] = {}

# key → manifest of the last published blob (header digest, per-leaf
# digests/codecs/frame offsets) — what a delta publish diffs against.
# Process-local by design: the publisher of an RL weight-sync loop is one
# long-lived process, and a manifest the STORE disagrees with just costs
# one 409 + full re-publish (self-healing).
_PUBLISH_MANIFESTS: Dict[str, dict] = {}


def last_restore_stats() -> Dict[str, float]:
    """Decomposition of the most recent streamed restore: wall/fetch/place
    seconds, bytes (wire vs decoded), codec/dequant seconds, leaves, and
    the fetch/placement overlap ratio."""
    return dict(_LAST_RESTORE)


def last_publish_stats() -> Dict[str, float]:
    """Decomposition of the most recent put_arrays publish: wire vs raw
    bytes, encode seconds, and (for delta publishes) leaves skipped."""
    return dict(_LAST_PUBLISH)


def _dtype_from_name(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _tree_flatten(tree: Any):
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def _pack_header(host_leaves, treedef) -> bytes:
    header = {
        "treedef": str(treedef),
        # dtype by name: ml_dtypes types (bfloat16, fp8) stringify as 'V2'
        # through .str, but round-trip cleanly by name.
        "leaves": [{"shape": list(a.shape), "dtype": a.dtype.name}
                   for a in host_leaves],
    }
    head = msgpack.packb(header)
    return _MAGIC + len(head).to_bytes(8, "little") + head


def device_get_chunked(leaves, chunk_bytes: int = 256 << 20):
    """Device→host fetch of many arrays in O(total/chunk) transfers
    instead of O(leaves).

    Each ``jax.device_get`` pays a per-call fixed cost (dispatch +
    transfer setup); a param tree has hundreds of leaves, so per-leaf
    fetches turn the staging hop into n_leaves × fixed-cost. Packing leaves
    (grouped by dtype) into ≤``chunk_bytes`` on-device buffers cuts the
    call count to a handful; the on-device concatenate is an HBM copy,
    orders of magnitude faster than any host link. Multi-device-sharded
    leaves fall back to the direct fetch (concatenating across meshes
    would force a gather the caller didn't ask for).
    """
    import jax
    import jax.numpy as jnp

    out = [None] * len(leaves)
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        if not isinstance(leaf, jax.Array) or len(leaf.devices()) > 1:
            out[i] = np.asarray(jax.device_get(leaf))
            continue
        # group by (dtype, device): concatenating same-dtype leaves
        # committed to DIFFERENT devices raises — those batch per device.
        # The device OBJECT is the key (ids are only unique per backend:
        # cpu:0 and tpu:0 would collide on .id)
        dev = next(iter(leaf.devices()))
        groups.setdefault((leaf.dtype, dev), []).append(i)

    def flush(batch):
        if not batch:
            return
        if len(batch) == 1:
            i = batch[0]
            out[i] = np.asarray(jax.device_get(leaves[i]))
            return
        try:
            buf = jnp.concatenate([leaves[i].ravel() for i in batch])
        except Exception:
            # the packed buffer needs up to chunk_bytes of fresh
            # contiguous HBM — at-HBM-edge states (where this repo
            # deliberately runs) can refuse it; per-leaf staging is the
            # slow-but-safe fallback the old path always used
            for i in batch:
                out[i] = np.asarray(jax.device_get(leaves[i]))
            return
        host = np.asarray(jax.device_get(buf))
        off = 0
        for i in batch:
            n = leaves[i].size
            out[i] = host[off:off + n].reshape(leaves[i].shape)
            off += n

    for idxs in groups.values():
        batch, size = [], 0
        for i in idxs:
            if batch and size + leaves[i].nbytes > chunk_bytes:
                flush(batch)
                batch, size = [], 0
            batch.append(i)
            size += leaves[i].nbytes
        flush(batch)
    return out


def _host_leaves(tree: Any):
    leaves, treedef = _tree_flatten(tree)
    return device_get_chunked(leaves), treedef


def pack_arrays(tree: Any, codec: Optional[str] = None) -> bytes:
    """Pack a pytree of (jax/numpy) arrays into one buffer. ``codec``
    (None → ``KT_WIRE_CODEC`` → ``raw``) selects the wire codec; ``raw``
    emits the V1 format byte-identically to always, any other codec emits
    the framed V2 format (``data_store/codec.py``)."""
    codec = codec_mod.resolve_codec(codec)
    host_leaves, treedef = _host_leaves(tree)
    if codec == "raw":
        buf = io.BytesIO()
        buf.write(_pack_header(host_leaves, treedef))
        for array in host_leaves:
            buf.write(np.ascontiguousarray(array).tobytes())
        return buf.getvalue()
    codecs = [codec_mod.leaf_codec(codec, a) for a in host_leaves]
    return b"".join(codec_mod.pack_stream(str(treedef), host_leaves,
                                          codecs, codec_name=codec))


def iter_packed(tree: Any, chunk: int = 8 << 20,
                codec: Optional[str] = None):
    """Yield the packed form in chunks without materializing one giant
    buffer — a multi-GB param tree streams straight onto the wire (peak
    memory O(one encoded leaf) for compressing codecs)."""
    codec = codec_mod.resolve_codec(codec)
    host_leaves, treedef = _host_leaves(tree)
    if codec == "raw":
        yield _pack_header(host_leaves, treedef)
        for block in _iter_leaf_bytes(host_leaves, chunk):
            yield bytes(block)
        return
    codecs = [codec_mod.leaf_codec(codec, a) for a in host_leaves]
    yield from codec_mod.pack_stream(str(treedef), host_leaves, codecs,
                                     codec_name=codec)


def _iter_leaf_bytes(host_leaves, chunk: int = 32 << 20):
    """Zero-copy memoryview chunks over the leaves' raw bytes."""
    for array in host_leaves:
        # uint8 view: ml_dtypes dtypes (bfloat16/fp8) have no buffer
        # protocol of their own, but any contiguous array views as bytes
        flat = np.ascontiguousarray(array).reshape(-1).view(np.uint8)
        mv = memoryview(flat)
        for i in range(0, len(mv), chunk):
            yield mv[i:i + chunk]


def unpack_arrays(data: bytes, template: Optional[Any] = None,
                  copy: bool = False) -> Any:
    """Unpack to numpy leaves; structure comes from ``template`` when given
    (exact pytree round-trip), else a flat list.

    ``copy=False`` (default) returns zero-copy ``np.frombuffer`` views into
    ``data`` — fastest, but every view pins the ENTIRE blob: one surviving
    1 KB leaf keeps a multi-GB buffer alive. ``copy=True`` materializes
    each leaf into its own freshly-owned array so ``data`` is collectable
    the moment this returns — what :func:`get_arrays` uses on its blocking
    fallback (and what the streaming path gets for free, since streamed
    leaves are assembled into owned buffers, never views).

    Both wire formats decode: V1 (uncodec'd) and codec-framed V2, where
    non-raw leaves always come back as owned arrays (decompressed /
    host-dequantized) regardless of ``copy``."""
    import jax

    head = bytes(data[:len(_MAGIC)])
    if head == codec_mod.MAGIC_V2:
        leaves = _unpack_v2(data, copy)
    elif head == _MAGIC:
        # memoryview slices: bytes slicing would COPY each multi-GB leaf
        mv = memoryview(data)
        offset = len(_MAGIC)
        head_len = int.from_bytes(mv[offset:offset + 8], "little")
        offset += 8
        header = msgpack.unpackb(mv[offset:offset + head_len])
        offset += head_len
        leaves = []
        for spec in header["leaves"]:
            dtype = _dtype_from_name(spec["dtype"])
            count = int(np.prod(spec["shape"])) if spec["shape"] else 1
            nbytes = count * dtype.itemsize
            array = np.frombuffer(
                mv[offset:offset + nbytes],
                dtype=dtype).reshape(spec["shape"])
            if copy:
                array = np.array(array)  # owns its memory; frees the blob
            leaves.append(array)
            offset += nbytes
    else:
        raise ValueError("not a packed-array buffer")
    if template is not None:
        treedef = jax.tree.structure(template)
        return jax.tree.unflatten(treedef, leaves)
    return leaves


def _unpack_v2(data, copy: bool) -> List[np.ndarray]:
    """Decode a codec-framed V2 blob to host leaves (host dequant)."""
    mv = memoryview(data)
    header, offset = codec_mod.parse_header(mv)
    leaves = []
    for spec in header["leaves"]:
        dtype = _dtype_from_name(spec["dtype"])
        enc = int.from_bytes(mv[offset:offset + 8], "little")
        offset += 8
        name = spec.get("codec", "raw")
        if name == "raw":
            count = int(np.prod(spec["shape"])) if spec["shape"] else 1
            if enc != count * dtype.itemsize:
                raise ValueError(
                    f"raw leaf frame {enc} bytes != shape's "
                    f"{count * dtype.itemsize}")
            array = np.frombuffer(
                mv[offset:offset + enc], dtype=dtype).reshape(spec["shape"])
            if copy:
                array = np.array(array)
        else:
            dec = codec_mod.make_decoder(spec, dtype)
            dec.feed(mv[offset:offset + enc])
            array = dec.finish()
        leaves.append(array)
        offset += enc
    if offset != len(mv):
        raise ValueError(
            f"blob carries {len(mv) - offset} bytes past the last leaf")
    return leaves


class StreamUnpacker:
    """Incremental parser for the packed-array wire format.

    Feed it chunks as they come off the socket; it hands back complete
    leaves as soon as their last byte arrives. Peak buffering is
    O(header + chunk + current leaf): incoming bytes are copied straight
    into each leaf's own freshly-allocated buffer (so, unlike
    ``unpack_arrays``'s views, finished leaves never pin the stream), and
    the only other storage is the pre-header accumulation buffer plus
    whatever tail of the current chunk hasn't been consumed yet —
    the whole blob is never materialized.

    Speaks both wire formats: V1 (uncodec'd) and codec-framed V2, whose
    leaves decode incrementally (zlib/zstd inflate straight into the leaf
    buffer; int8 accumulates the small scales+q representation).
    ``device_dequant=True`` hands int8 leaves back as
    :class:`~kubetorch_tpu.data_store.codec.QuantLeaf` so the placement
    pipeline can ship the SMALL form over PCIe and dequantize on device;
    the default dequantizes on host and always yields ndarrays.
    """

    def __init__(self, device_dequant: bool = False):
        self._pending = bytearray()   # unparsed bytes before the header ends
        self.header: Optional[dict] = None
        self._specs: List[Tuple[tuple, np.dtype, int]] = []
        self._leaf_ix = 0
        self._cur: Optional[np.ndarray] = None   # flat uint8 view being filled
        self._cur_arr: Optional[np.ndarray] = None
        self._cur_off = 0
        self.bytes_fed = 0
        self.peak_buffered = 0  # max(pending + current-leaf allocation)
        # V2 state
        self._v2 = False
        self._device_dequant = device_dequant
        self._leafspecs: List[Tuple[dict, np.dtype]] = []
        self._prefix = bytearray()     # partial u64 frame-length prefix
        self._dec = None               # active leaf decoder
        self._dec_left = 0
        self.decode_s = 0.0            # time in non-raw codec decoders
        self.raw_bytes = 0             # decoded (pre-codec) payload total

    @property
    def num_leaves(self) -> Optional[int]:
        if self.header is None:
            return None
        return len(self._leafspecs) if self._v2 else len(self._specs)

    @property
    def complete(self) -> bool:
        if self.header is None:
            return False
        if self._v2:
            return (self._leaf_ix >= len(self._leafspecs)
                    and self._dec is None and not self._prefix
                    and not self._pending)
        return (self._leaf_ix >= len(self._specs)
                and not self._pending)

    def _note_buffered(self):
        if self._v2:
            cur = self._dec.buffered if self._dec is not None else 0
        else:
            cur = self._cur.nbytes if self._cur is not None else 0
        self.peak_buffered = max(self.peak_buffered,
                                 len(self._pending) + cur)

    def _start_leaf(self) -> List[Tuple[int, np.ndarray]]:
        """Allocate the next leaf buffer; emit any zero-byte leaves."""
        done = []
        while self._leaf_ix < len(self._specs):
            shape, dtype, nbytes = self._specs[self._leaf_ix]
            if nbytes == 0:
                done.append((self._leaf_ix,
                             np.empty(shape, dtype=dtype)))
                self._leaf_ix += 1
                continue
            arr = np.empty(shape, dtype=dtype)
            self._cur_arr = arr
            self._cur = arr.reshape(-1).view(np.uint8).reshape(-1)
            self._cur_off = 0
            break
        return done

    def _parse_header(self) -> bool:
        base = len(_MAGIC) + 8
        if len(self._pending) < len(_MAGIC):
            return False
        magic = bytes(self._pending[:len(_MAGIC)])
        if magic not in (_MAGIC, codec_mod.MAGIC_V2):
            raise ValueError("not a packed-array stream")
        if len(self._pending) < base:
            return False
        head_len = int.from_bytes(self._pending[len(_MAGIC):base], "little")
        if len(self._pending) < base + head_len:
            return False
        self.header = msgpack.unpackb(bytes(
            self._pending[base:base + head_len]))
        self._v2 = magic == codec_mod.MAGIC_V2
        for spec in self.header["leaves"]:
            dtype = _dtype_from_name(spec["dtype"])
            count = int(np.prod(spec["shape"])) if spec["shape"] else 1
            self.raw_bytes += count * dtype.itemsize
            if self._v2:
                self._leafspecs.append((spec, dtype))
            else:
                self._specs.append(
                    (tuple(spec["shape"]), dtype, count * dtype.itemsize))
        del self._pending[:base + head_len]
        return True

    def feed(self, data) -> List[Tuple[int, np.ndarray]]:
        """Consume one chunk; return the ``(leaf_index, array)`` pairs that
        completed inside it (possibly none, possibly several). In
        ``device_dequant`` mode int8-coded leaves arrive as
        :class:`~kubetorch_tpu.data_store.codec.QuantLeaf` instead of
        ndarrays."""
        mv = memoryview(data)
        if mv.ndim != 1 or mv.itemsize != 1:
            mv = mv.cast("B")
        self.bytes_fed += len(mv)
        out: List[Tuple[int, np.ndarray]] = []
        off = 0
        if self.header is None:
            self._pending += mv
            self._note_buffered()
            if not self._parse_header():
                return out
            if not self._v2:
                out.extend(self._start_leaf())
            # the header tail may carry leaf bytes: drain pending below
            mv = memoryview(bytes(self._pending))
            self._pending.clear()
        if self._v2:
            self._feed_v2(mv, out)
            return out
        while off < len(mv):
            if self._cur is None:
                if self._leaf_ix >= len(self._specs):
                    raise ValueError(
                        f"stream carries {len(mv) - off} bytes past the "
                        f"declared leaves")
                out.extend(self._start_leaf())
                if self._cur is None:
                    continue
            take = min(len(mv) - off, len(self._cur) - self._cur_off)
            self._cur[self._cur_off:self._cur_off + take] = \
                np.frombuffer(mv[off:off + take], dtype=np.uint8)
            self._cur_off += take
            off += take
            if self._cur_off == len(self._cur):
                out.append((self._leaf_ix, self._cur_arr))
                self._leaf_ix += 1
                self._cur = self._cur_arr = None
                out.extend(self._start_leaf())
            self._note_buffered()
        return out

    def _feed_v2(self, mv, out: List[Tuple[int, Any]]) -> None:
        """Frame loop for the codec'd format: ``u64 enc | payload`` per
        leaf, payload bytes fed straight to the leaf's decoder."""
        off = 0
        n = len(self._leafspecs)
        while off < len(mv):
            if self._dec is None:
                if self._leaf_ix >= n:
                    raise ValueError(
                        f"stream carries {len(mv) - off} bytes past the "
                        f"declared leaves")
                take = min(8 - len(self._prefix), len(mv) - off)
                self._prefix += mv[off:off + take]
                off += take
                if len(self._prefix) < 8:
                    return
                enc = int.from_bytes(self._prefix, "little")
                self._prefix.clear()
                spec, dtype = self._leafspecs[self._leaf_ix]
                self._dec = codec_mod.make_decoder(
                    spec, dtype, self._device_dequant)
                self._dec_left = enc
                self._note_buffered()
                if enc == 0:
                    out.append(self._finish_leaf())
                continue
            take = min(self._dec_left, len(mv) - off)
            if self._dec.timed:
                t0 = time.perf_counter()
                self._dec.feed(mv[off:off + take])
                self.decode_s += time.perf_counter() - t0
            else:
                self._dec.feed(mv[off:off + take])
            off += take
            self._dec_left -= take
            if self._dec_left == 0:
                out.append(self._finish_leaf())

    def _finish_leaf(self) -> Tuple[int, Any]:
        if self._dec.timed:
            t0 = time.perf_counter()
            item = self._dec.finish()
            self.decode_s += time.perf_counter() - t0
        else:
            item = self._dec.finish()
        ix = self._leaf_ix
        self._leaf_ix += 1
        self._dec = None
        return ix, item

    def finish(self):
        """Raise unless every declared leaf arrived in full."""
        if self.header is None:
            raise ValueError("stream ended before the header completed")
        if self._v2:
            if (self._dec is not None or self._prefix
                    or self._leaf_ix < len(self._leafspecs)):
                raise ValueError(
                    f"stream ended at leaf {self._leaf_ix}/"
                    f"{len(self._leafspecs)} (short read)")
            return
        if self._cur is not None or self._leaf_ix < len(self._specs):
            raise ValueError(
                f"stream ended at leaf {self._leaf_ix}/"
                f"{len(self._specs)} (short read)")


def iter_unpack_arrays(chunks: Iterable) -> Iterable[Tuple[int, np.ndarray]]:
    """Streaming twin of :func:`unpack_arrays`: yield ``(leaf_index,
    array)`` pairs as each leaf's bytes arrive from ``chunks``, without
    ever holding the whole blob (peak memory O(chunk + largest leaf)).
    Yielded arrays own their memory. Raises on a short stream."""
    unpacker = StreamUnpacker()
    for chunk in chunks:
        for item in unpacker.feed(chunk):
            yield item
    unpacker.finish()


def _record_publish(stats: Dict[str, float]) -> None:
    _LAST_PUBLISH.clear()
    _LAST_PUBLISH.update(stats)
    try:
        from kubetorch_tpu.observability.prometheus import record_wire

        record_wire({
            "tx_bytes": stats.get("wire_bytes", 0),
            "tx_raw_bytes": stats.get("raw_bytes", 0),
            "encode_s": stats.get("encode_s", 0.0),
            "delta_publish": stats.get("delta", 0.0),
            "delta_leaves_skipped": stats.get("leaves_skipped", 0),
            "delta_fallback": stats.get("delta_fallback", 0.0),
        })
    # ktlint: disable=KT004 -- metrics must never fail a publish
    except Exception:
        pass


def put_arrays(key: str, tree: Any, codec: Optional[str] = None,
               delta: Optional[bool] = None,
               store_url: Optional[str] = None) -> str:
    """Publish a pytree of arrays (params, state dicts) under ``key``.

    ``codec`` (None → ``KT_WIRE_CODEC`` → ``raw``) picks the wire codec:
    ``raw`` ships the V1 format unchanged; ``zlib``/``zstd`` compress
    losslessly (payload size unknown upfront → the upload switches to
    chunked transfer-encoding so Content-Length can never lie about the
    encoded stream); ``int8`` quantizes float leaves per row (~2-4× fewer
    bytes, everything else stays raw/bit-exact).

    ``delta`` (None → ``KT_WIRE_DELTA`` → off) enables **delta publish**:
    per-leaf content digests are kept for the last published version of
    ``key`` and the next publish ships only changed leaves as a byte
    patch the store splices against its current blob — a LoRA-only or
    frozen-backbone update is kilobytes, not gigabytes. A store that no
    longer holds the expected base (404/409) silently degrades to a full
    publish; :func:`last_publish_stats` reports the decomposition.

    ``store_url`` overrides the destination store for this one publish
    (direct pod-to-pod push: a prefill pod PUTs an exported row at the
    *decode* pod's store endpoint instead of its own default store).
    """
    from kubetorch_tpu.data_store.client import DataStoreClient

    codec = codec_mod.resolve_codec(codec)
    delta = codec_mod.delta_enabled(delta)
    client = (DataStoreClient(store_url) if store_url
              else DataStoreClient.default())
    backend = client._backend()
    with tracing.span("store.put_arrays",
                      attrs={"key": key, "codec": codec,
                             "delta": bool(delta)}):
        return _put_arrays(key, tree, codec, delta, backend)


def _put_arrays(key: str, tree: Any, codec: str, delta: bool,
                backend) -> str:
    t_start = time.perf_counter()
    host_leaves, treedef = _host_leaves(tree)
    raw_bytes = sum(a.nbytes for a in host_leaves)

    if codec == "raw" and not delta:
        # the V1 fast path, byte-identical to always; an untracked
        # publish breaks any recorded delta chain for the key
        _PUBLISH_MANIFESTS.pop(key, None)
        header = _pack_header(host_leaves, treedef)
        total = len(header) + raw_bytes
        if not hasattr(backend, "put_blob_stream"):
            buf = io.BytesIO()
            buf.write(header)
            for array in host_leaves:
                buf.write(np.ascontiguousarray(array).tobytes())
            backend.put_blob(key, buf.getvalue())
        else:
            def chunks():
                # A GENERATOR FUNCTION, not a generator: put_blob_stream
                # invokes the factory once per retry attempt, so every
                # attempt re-yields the header before the leaf bytes.
                # Handing it a single exhausted generator would make a
                # retried publish stream leaf bytes with no header (or
                # nothing at all) — the backend guards against that.
                yield header
                yield from _iter_leaf_bytes(host_leaves)

            # known total length → the store's raw sendall path: leaf
            # bytes go memoryview→socket with zero copies (publish used
            # to trail raw blob-put by ~28% purely on pack/frame copies)
            backend.put_blob_stream(key, chunks, length=total)
        _record_publish({
            "wall_s": time.perf_counter() - t_start,
            "wire_bytes": total, "raw_bytes": raw_bytes,
            "encode_s": 0.0, "leaves": len(host_leaves),
            "leaves_sent": len(host_leaves), "leaves_skipped": 0,
            "delta": 0.0, "codec": 0.0})
        return key

    codecs = [codec_mod.leaf_codec(codec, a) for a in host_leaves]
    digests = ([codec_mod.leaf_digest(a) for a in host_leaves]
               if delta else None)
    treedef_str = str(treedef)
    delta_fallback = 0.0
    prev = _PUBLISH_MANIFESTS.get(key) if delta else None
    if (prev is not None and prev.get("treedef") == treedef_str
            and hasattr(backend, "put_blob_delta")):
        built = codec_mod.build_delta(prev, treedef_str, host_leaves,
                                      codecs, digests)
        if built is not None:
            delta_blob, manifest, stats = built
            try:
                backend.put_blob_delta(key, delta_blob)
            except DataStoreError as exc:
                # base drifted under us (store restart, concurrent
                # publisher, retention sweep): full publish heals the
                # chain. Anything else is a real error.
                if getattr(exc, "status", None) not in (404, 409):
                    raise
                delta_fallback = 1.0
            else:
                manifest["treedef"] = treedef_str
                _PUBLISH_MANIFESTS[key] = manifest
                _record_publish({
                    "wall_s": time.perf_counter() - t_start,
                    "wire_bytes": stats["wire_bytes"],
                    "raw_bytes": raw_bytes,
                    "encode_s": stats["encode_s"],
                    "leaves": stats["leaves_total"],
                    "leaves_sent": stats["leaves_sent"],
                    "leaves_skipped": stats["leaves_skipped"],
                    "delta": 1.0, "codec": 1.0})
                return key

    record: Dict[str, Any] = {}

    def chunks():
        # fresh generator per retry attempt; ``record`` is reset inside
        # pack_stream, so a retried publish re-records its manifest
        yield from codec_mod.pack_stream(
            treedef_str, host_leaves, codecs, digests=digests,
            record=record, codec_name=codec)

    metas = [codec_mod.leaf_meta(c, a)
             for c, a in zip(codecs, host_leaves)]
    if hasattr(backend, "put_blob_stream"):
        header_len = len(codec_mod.build_header(
            treedef_str, metas, codec, digests))
        # size-deterministic codecs (raw/int8) keep the zero-copy
        # Content-Length sendall path; compressors MUST go chunked — a
        # declared length may never disagree with the encoded stream
        total = codec_mod.packed_size(host_leaves, codecs, header_len)
        backend.put_blob_stream(key, chunks, length=total)
    else:
        backend.put_blob(key, b"".join(chunks()))
    if delta:
        _PUBLISH_MANIFESTS[key] = {
            "hdr_digest": record["hdr_digest"], "total": record["total"],
            "digests": digests, "codecs": codecs, "metas": metas,
            "frames": record["frames"], "codec": codec,
            "treedef": treedef_str}
    _record_publish({
        "wall_s": time.perf_counter() - t_start,
        "wire_bytes": record.get("total", 0), "raw_bytes": raw_bytes,
        "encode_s": record.get("encode_s", 0.0),
        "leaves": len(host_leaves), "leaves_sent": len(host_leaves),
        "leaves_skipped": 0, "delta": 0.0,
        "delta_fallback": delta_fallback, "codec": 1.0})
    return key


class _PlacementPipeline:
    """Background host→device placement for the streaming restore.

    The producer (network thread) enqueues batches of completed host
    leaves; this thread issues one coalesced ``jax.device_put`` per batch
    (a list of arrays + one sharding — a single dispatch, the restore
    mirror of ``device_get_chunked``). The bounded queue double-buffers:
    one batch in flight on the device link while the next fills from the
    wire, so transfer-setup time hides under network time instead of
    adding to it.
    """

    def __init__(self, out: List, depth: int = 2):
        self.out = out
        self.queue: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self.error: Optional[BaseException] = None
        self.place_s = 0.0
        self.dequant_s = 0.0
        self.leaves_placed = 0
        self.bytes_placed = 0
        # copy_context: a bare Thread starts from an EMPTY context, so
        # the restore's request_id_var and ambient trace span would both
        # vanish here — restore log lines from this thread carried
        # request_id="-", and device_put spans would start orphan traces
        # instead of nesting under store.get_arrays.
        ctx = contextvars.copy_context()
        self._thread = threading.Thread(
            target=lambda: ctx.run(self._run),
            name="kt-restore-place", daemon=True)
        self._thread.start()

    def _run(self):
        import jax

        while True:
            item = self.queue.get()
            if item is None:
                return
            if self.error is not None:
                continue  # drain so the producer never blocks forever
            idxs, arrays, sharding, scale_sh = item
            t0 = time.perf_counter()
            wall0 = time.time()
            dequant_d = 0.0
            try:
                if scale_sh is not None:
                    # int8-coded batch: ship the SMALL representation over
                    # the host→device link (q leaf-shaped + per-row
                    # scales), dequantize in a jitted kernel on device —
                    # PCIe carries ~1/4 the bytes of the bf16/f32 leaves
                    qs = jax.device_put([l.q for l in arrays], sharding)
                    ss = jax.device_put([l.scale for l in arrays],
                                        scale_sh)
                    jax.block_until_ready((qs, ss))
                    t1 = time.perf_counter()
                    placed = [
                        _dequant_fn(l.dtype.name, sharding)(q, s)
                        for l, q, s in zip(arrays, qs, ss)]
                    jax.block_until_ready(placed)
                    dequant_d = time.perf_counter() - t1
                    self.dequant_s += dequant_d
                else:
                    placed = jax.device_put(arrays, sharding)
                    # block HERE, on the pipeline thread: device_put
                    # returns before the copy lands, so without this the
                    # next batch's host buffers could be freed/reused
                    # mid-transfer and place_s would measure dispatch, not
                    # transfer. The main thread keeps draining the wire.
                    jax.block_until_ready(placed)
            except BaseException as exc:  # surfaced in close()/submit()
                self.error = exc
                continue
            batch_s = time.perf_counter() - t0
            self.place_s += batch_s
            # one span per coalesced batch, timed over EXACTLY the
            # interval summed into place_s — so a trace's device_put
            # spans reconcile with the restore_last_place_seconds gauge
            tracing.record_span(
                "restore.device_put", batch_s, start=wall0,
                attrs={"leaves": len(idxs),
                       "bytes": sum(a.nbytes for a in arrays)})
            if dequant_d > 0.0:
                tracing.record_span(
                    "restore.dequant", dequant_d,
                    attrs={"leaves": len(idxs)})
            for i, arr in zip(idxs, placed):
                self.out[i] = arr
            self.leaves_placed += len(idxs)
            self.bytes_placed += sum(a.nbytes for a in arrays)

    def submit(self, idxs: List[int], arrays: List, sharding,
               scale_sh=None):
        if self.error is not None:
            raise self.error
        self.queue.put((idxs, arrays, sharding, scale_sh))

    def close(self):
        self.queue.put(None)
        self._thread.join()
        if self.error is not None:
            raise self.error


def _flat_shardings(shardings: Any, template: Optional[Any],
                    n_leaves: int) -> List[Any]:
    """Per-leaf sharding list from the user-facing ``shardings`` arg (a
    single Sharding/device applied to every leaf, or a pytree matching
    ``template``)."""
    import jax

    structured = isinstance(shardings, (list, dict, tuple)) or hasattr(
        shardings, "keys")
    if not structured:
        return [shardings] * n_leaves
    if template is not None:
        flat = jax.tree.structure(template).flatten_up_to(shardings)
    else:
        flat = list(shardings)
    if len(flat) != n_leaves:
        raise ValueError(
            f"shardings tree has {len(flat)} leaves; stream carries "
            f"{n_leaves}")
    return flat


def _sharding_group_key(dtype: np.dtype, sharding) -> tuple:
    try:
        hash(sharding)
        return (dtype.name, sharding)
    except TypeError:
        return (dtype.name, id(sharding))


@functools.lru_cache(maxsize=None)
def _dequant_fn(dtype_name: str, sharding=None):
    """Jitted on-device dequant for int8-coded leaves: q (leaf-shaped
    int8) × per-row float32 scale → target dtype. One compile per
    (dtype, sharding, shape) — a param tree has a handful of shapes,
    amortized across every weight-sync round. ``out_shardings`` pins the
    result to the CALLER'S requested layout: without it the compiler
    picks, and a layout that differs from ``get_arrays``' contract would
    cost a silent reshard in the consumer's jitted step every round."""
    import jax
    import jax.numpy as jnp

    dt = _dtype_from_name(dtype_name)

    def f(q, s):
        cols = q.shape[-1] if q.ndim else 1
        qr = q.reshape(-1, cols).astype(jnp.float32) * s[:, None]
        return qr.astype(dt).reshape(q.shape)

    if sharding is not None:
        try:
            return jax.jit(f, out_shardings=sharding)
        except TypeError:  # very old jax: fall back to compiler choice
            pass
    return jax.jit(f)


def _scale_sharding(sharding):
    """Sharding for an int8 leaf's per-row scales (shape differs from the
    leaf's): reuse a SingleDeviceSharding as-is, replicate over a
    NamedSharding's mesh; None → the leaf host-dequantizes instead."""
    try:
        import jax

        if isinstance(sharding, jax.sharding.SingleDeviceSharding):
            return sharding
        if isinstance(sharding, jax.sharding.NamedSharding):
            return jax.sharding.NamedSharding(
                sharding.mesh, jax.sharding.PartitionSpec())
    # ktlint: disable=KT004 -- probe: caller handles the None fallback
    except Exception:
        pass
    return None


def _streamed_restore(chunks: Iterable, template: Optional[Any],
                      shardings: Optional[Any],
                      batch_bytes: int = 64 << 20,
                      pipeline_depth: int = 2,
                      wire_bytes: Optional[int] = None,
                      pre_fetch_s: float = 0.0,
                      delta_hit: Optional[bool] = None) -> Any:
    """Assemble leaves from a chunk stream and place them as they land.

    Completed leaves batch per (dtype, sharding) up to ``batch_bytes``;
    each full batch goes to the placement thread while the wire keeps
    filling the next — fetch and host→device transfer overlap instead of
    summing. int8-coded leaves stay in their small (q, scale) form all
    the way onto the device (jitted dequant there); everything else
    arrives as decoded host arrays. Peak host memory is O(chunk + largest
    leaf + pipeline_depth × batch_bytes), never O(total blob).

    ``wire_bytes``/``pre_fetch_s``/``delta_hit``: when the chunk stream
    reads a locally spliced/teed file rather than the wire itself, the
    caller passes what the network actually carried so the stats stay
    honest.
    """
    import jax

    t_start = time.perf_counter()
    unpacker = StreamUnpacker(device_dequant=shardings is not None)
    out: List[Any] = []
    flat_sh: Optional[List[Any]] = None
    pipeline: Optional[_PlacementPipeline] = None
    # group key → [indices, arrays, nbytes, sharding, scale_sharding]
    groups: Dict[tuple, list] = {}
    fetch_s = 0.0
    bytes_streamed = 0

    def on_leaf(ix: int, arr):
        nonlocal pipeline
        quant = isinstance(arr, codec_mod.QuantLeaf)
        if flat_sh is None or flat_sh[ix] is None:
            out[ix] = arr.dequant() if quant else arr
            return
        sharding = flat_sh[ix]
        scale_sh = _scale_sharding(sharding) if quant else None
        if quant and scale_sh is None:
            # no replicable scale layout for this sharding type: host
            # dequant, then the ordinary placement path
            arr = arr.dequant()
            quant = False
        if pipeline is None:
            pipeline = _PlacementPipeline(out, depth=pipeline_depth)
        key = ((("q8",) if quant else ())
               + _sharding_group_key(np.dtype(arr.dtype), sharding))
        group = groups.setdefault(key, [[], [], 0, sharding, scale_sh])
        group[0].append(ix)
        group[1].append(arr)
        group[2] += arr.nbytes
        if group[2] >= batch_bytes:
            pipeline.submit(group[0], group[1], group[3], group[4])
            del groups[key]

    try:
        it = iter(chunks)
        while True:
            t0 = time.perf_counter()
            try:
                chunk = next(it)
            except StopIteration:
                fetch_s += time.perf_counter() - t0
                break
            fetch_s += time.perf_counter() - t0
            bytes_streamed += len(chunk)
            completed = unpacker.feed(chunk)
            if out == [] and unpacker.header is not None:
                n = unpacker.num_leaves
                out = [None] * n
                if shardings is not None:
                    flat_sh = _flat_shardings(shardings, template, n)
            for ix, arr in completed:
                on_leaf(ix, arr)
        unpacker.finish()
        if unpacker.num_leaves == 0:
            out = []
        for group in groups.values():
            assert pipeline is not None
            pipeline.submit(group[0], group[1], group[3], group[4])
        groups.clear()
    except BaseException:
        if pipeline is not None:
            try:
                pipeline.close()
            # ktlint: disable=KT004 -- the original error is the one to surface
            except BaseException:
                pass
        raise
    place_s = 0.0
    dequant_s = 0.0
    if pipeline is not None:
        pipeline.close()
        place_s = pipeline.place_s
        dequant_s = pipeline.dequant_s
    wall_s = time.perf_counter() - t_start
    # dataplane spans: the fetch loop (time blocked on the wire/file) and
    # the incremental codec decode, timed from the already-instrumented
    # accumulators — together with the pipeline thread's device_put
    # spans these are the per-restore tree "where did it go" view
    tracing.record_span(
        "restore.fetch", fetch_s,
        start=time.time() - wall_s,
        attrs={"bytes": bytes_streamed,
               "leaves": unpacker.num_leaves or 0})
    if unpacker.decode_s > 0.0:
        tracing.record_span("restore.decode", unpacker.decode_s,
                            attrs={"raw_bytes": unpacker.raw_bytes})
    # Fraction of placement time hidden under the fetch: 1.0 = placement
    # fully overlapped (wall ≈ fetch), 0.0 = serial fetch-then-place.
    hidden = fetch_s + place_s - wall_s
    overlap = max(0.0, min(1.0, hidden / place_s)) if place_s > 1e-9 else 1.0
    _LAST_RESTORE.clear()
    _LAST_RESTORE.update({
        "wall_s": wall_s + pre_fetch_s, "fetch_s": fetch_s + pre_fetch_s,
        "place_s": place_s,
        "bytes_streamed": bytes_streamed,
        "wire_bytes": bytes_streamed if wire_bytes is None else wire_bytes,
        "raw_bytes": unpacker.raw_bytes,
        "codec_decode_s": unpacker.decode_s,
        "dequant_s": dequant_s,
        "leaves": len(out),
        "leaves_placed": pipeline.leaves_placed if pipeline else 0,
        "overlap_ratio": round(overlap, 4),
        "peak_buffered_bytes": unpacker.peak_buffered,
        "streaming": 1.0,
    })
    if delta_hit is not None:
        _LAST_RESTORE["delta_hit"] = 1.0 if delta_hit else 0.0
    try:
        from kubetorch_tpu.observability.prometheus import (
            record_restore,
            record_wire,
        )

        record_restore(_LAST_RESTORE)
        record_wire({
            "rx_bytes": _LAST_RESTORE["wire_bytes"],
            "rx_raw_bytes": unpacker.raw_bytes,
            "decode_s": unpacker.decode_s, "dequant_s": dequant_s,
            "delta_fetch_hit": 1.0 if delta_hit else 0.0,
            "delta_fetch_miss": 1.0 if delta_hit is False else 0.0,
        })
    # ktlint: disable=KT004 -- metrics must never fail a restore
    except Exception:
        pass
    if template is not None:
        return jax.tree.unflatten(jax.tree.structure(template), out)
    return out


def _splice_base_candidates(key: str) -> List[Path]:
    """Local files that might hold the previous version of ``key``'s
    blob — the restore cache first, then the broadcast peer cache (a
    fan-out member's last fetched copy works as a splice base too)."""
    out = []
    cache = codec_mod.restore_cache_root() / key
    if cache.is_file():
        out.append(cache)
    try:
        from kubetorch_tpu.data_store.broadcast import peer_cache_candidates

        out.extend(peer_cache_candidates(key))
    # ktlint: disable=KT004 -- optional peer cache: base list may be empty
    except Exception:
        pass
    return out


def _try_delta_splice(backend, key: str):
    """Fetch-side delta: if the store's patch sidecar names a base we
    hold locally (restore or peer cache), pull the patch and splice the
    full blob into the restore cache. Returns ``(cache_path,
    wire_bytes)`` or None (no sidecar / no matching base / cache dir
    unusable — caller full-fetches).

    The patch streams: the msgpack plan sits in the first frames, so a
    base mismatch aborts after ~one chunk instead of paying the whole
    patch on top of the full fetch it falls back to."""
    cache = codec_mod.restore_cache_root() / key
    try:
        cache.parent.mkdir(parents=True, exist_ok=True)
    except OSError:
        return None
    candidates = _splice_base_candidates(key)
    if not candidates:
        return None
    patch_key = key + BLOB_DELTA_SUFFIX
    buf = bytearray()
    base = None
    it = None
    try:
        if hasattr(backend, "get_blob_stream"):
            it = backend.get_blob_stream(patch_key, chunk_bytes=256 << 10)
        else:
            it = iter([backend.get_blob(patch_key)])
        plan = None
        for chunk in it:
            buf += chunk
            if plan is None and len(buf) >= 16:
                if bytes(buf[:8]) != codec_mod.MAGIC_DELTA:
                    return None
                plan_len = int.from_bytes(buf[8:16], "little")
                if len(buf) < 16 + plan_len:
                    continue
                plan, _ = codec_mod.parse_delta_plan(buf)
                data_bytes = sum(op[1] for op in plan["ops"]
                                 if op[0] == 0)
                if data_bytes > plan["new_len"] * 0.5:
                    # mostly-changed patch: the full STREAMED fetch is
                    # better than buffering a near-full-size patch in RAM
                    return None
                base = next(
                    (p for p in candidates
                     if p.stat().st_size == plan["base_len"]
                     and codec_mod.blob_header_digest(p)
                     == plan["base_hdr_digest"]), None)
                if base is None:
                    return None  # wrong generation: abort the download
        if plan is None or base is None:
            return None
    except (DataStoreError, OSError, ValueError):
        return None  # no sidecar (full put / pre-delta store) or corrupt
    finally:
        if it is not None:
            getattr(it, "close", lambda: None)()
    tmp = cache.with_name(f".{cache.name}.{_tmp_tag()}.tmp")
    try:
        codec_mod.splice_delta(bytes(buf), base, tmp)
        os.replace(tmp, cache)
    except (codec_mod.DeltaMismatch, ValueError, OSError):
        tmp.unlink(missing_ok=True)
        return None
    return cache, len(buf)


def _tmp_tag() -> str:
    """Unique per CALL, not per process: concurrent get_arrays of one
    key in threaded workers must not interleave into a shared tmp file
    (same rule as the store server's per-request staging names)."""
    import uuid

    return f"{os.getpid()}-{uuid.uuid4().hex[:8]}"


def _tee_to_cache(chunks: Iterable, cache: Path):
    """Pass wire chunks through to the streamed restore while appending
    them to the restore cache (tmp + atomic publish on completion) — the
    delta-miss fetch keeps PR 1's fetch/placement overlap instead of
    downloading to disk first, and the NEXT round can splice."""
    tmp = cache.with_name(f".{cache.name}.{_tmp_tag()}.tmp")
    try:
        fh = open(tmp, "wb")
    except OSError:
        yield from chunks  # unwritable cache: restore still works
        return
    try:
        for chunk in chunks:
            fh.write(chunk)
            yield chunk
    except BaseException:
        fh.close()
        tmp.unlink(missing_ok=True)
        raise
    fh.close()
    os.replace(tmp, cache)


def get_arrays(
    key: str,
    template: Optional[Any] = None,
    shardings: Optional[Any] = None,
    broadcast=None,
    *,
    streaming: Optional[bool] = None,
    chunk_bytes: Optional[int] = None,
    batch_bytes: int = 64 << 20,
    pipeline_depth: int = 2,
    delta: Optional[bool] = None,
) -> Any:
    """Fetch arrays; ``shardings`` (pytree of Sharding or a single one)
    device_puts each leaf — onto a *different* mesh/layout than the publisher
    used if desired. ``broadcast`` (a :class:`BroadcastWindow`) coordinates
    many simultaneous getters through the store's rolling fan-out tree — the
    RL weight-sync path at scale (reference: GPU broadcast groups,
    SURVEY.md §3.5).

    Restore is **streamed and pipelined** when the backend supports it
    (``streaming=None`` auto-detects; force with True/False): leaves are
    assembled from ``chunk_bytes``-sized reads as they arrive and handed to
    a background placement thread in coalesced per-(dtype, sharding)
    batches of up to ``batch_bytes`` (``pipeline_depth`` batches in
    flight), so wire time hides host→device transfer time and peak host
    memory stays O(chunk + largest leaf) instead of O(total blob). The
    blocking fallback fetches the whole blob, then unpacks with
    ``copy=True`` so the returned leaves never pin the fetched buffer.

    ``delta`` (None → ``KT_WIRE_DELTA`` → off) enables **delta fetch**:
    the fetcher keeps the last restored blob per key in the restore cache
    (``KT_RESTORE_CACHE``); when the store's delta sidecar names that
    cached blob (or a broadcast peer-cache copy) as its base, only the
    patch crosses the wire and unchanged leaves splice from disk. The
    codec is transparent on this side — V1 and codec-framed V2 blobs both
    restore, int8 leaves dequantizing on device when shardings are given.
    """
    with tracing.span("store.get_arrays",
                      attrs={"key": key,
                             "sharded": shardings is not None}):
        return _get_arrays(key, template, shardings, broadcast,
                           streaming=streaming, chunk_bytes=chunk_bytes,
                           batch_bytes=batch_bytes,
                           pipeline_depth=pipeline_depth, delta=delta)


def _get_arrays(key, template, shardings, broadcast, *, streaming,
                chunk_bytes, batch_bytes, pipeline_depth, delta):
    import jax

    from kubetorch_tpu.data_store.client import DataStoreClient

    chunk_bytes = chunk_bytes or codec_mod.default_chunk_bytes(8 << 20)
    delta = codec_mod.delta_enabled(delta) and broadcast is None
    backend = DataStoreClient.default()._backend()
    local_path = None
    wire_bytes: Optional[int] = None
    delta_hit: Optional[bool] = None
    pre_fetch_s = 0.0
    if delta:
        t0 = time.perf_counter()
        spliced = _try_delta_splice(backend, key)
        pre_fetch_s = time.perf_counter() - t0
        if spliced is not None:
            local_path, wire_bytes = spliced
            delta_hit = True
        else:
            delta_hit = False  # miss: full fetch, teed into the cache
    if streaming is None:
        streaming = (local_path is not None
                     or hasattr(backend, "get_blob_stream"))
    elif streaming and local_path is None and not hasattr(
            backend, "get_blob_stream"):
        raise DataStoreError(
            f"streaming=True but backend {type(backend).__name__} has no "
            f"get_blob_stream; use streaming=None to auto-fallback")
    if streaming:
        if local_path is not None:
            from kubetorch_tpu.data_store.http_store import (
                _iter_file_chunks,
            )

            chunks = _iter_file_chunks(local_path, chunk_bytes)
        else:
            chunks = backend.get_blob_stream(key, chunk_bytes=chunk_bytes,
                                             broadcast=broadcast)
            if delta:
                # tee the wire into the cache WHILE restoring — the miss
                # keeps fetch/placement overlapped, no fetch-then-read
                chunks = _tee_to_cache(
                    chunks, codec_mod.restore_cache_root() / key)
        return _streamed_restore(chunks, template, shardings,
                                 batch_bytes=batch_bytes,
                                 pipeline_depth=pipeline_depth,
                                 wire_bytes=wire_bytes,
                                 pre_fetch_s=pre_fetch_s,
                                 delta_hit=delta_hit)
    t0 = time.perf_counter()
    if local_path is not None:
        blob = local_path.read_bytes()
    else:
        blob = backend.get_blob(key, broadcast=broadcast)
        wire_bytes = len(blob)
        if delta:
            cache = codec_mod.restore_cache_root() / key
            tmp = cache.with_name(f".{cache.name}.{_tmp_tag()}.tmp")
            try:
                tmp.write_bytes(blob)
                os.replace(tmp, cache)
            except OSError:
                tmp.unlink(missing_ok=True)
    fetch_s = pre_fetch_s + time.perf_counter() - t0
    # copy=True: frombuffer views would keep the whole multi-GB blob
    # alive for as long as ANY returned leaf survives
    tree = unpack_arrays(blob, template, copy=(shardings is None))
    t1 = time.perf_counter()
    if shardings is not None:
        if isinstance(shardings, (list, dict, tuple)) or hasattr(
                shardings, "keys"):
            tree = jax.tree.map(jax.device_put, tree, shardings)
        else:
            tree = jax.tree.map(
                lambda x: jax.device_put(x, shardings), tree)
    place_s = time.perf_counter() - t1
    _LAST_RESTORE.clear()
    _LAST_RESTORE.update({
        "wall_s": fetch_s + place_s, "fetch_s": fetch_s,
        "place_s": place_s, "bytes_streamed": len(blob),
        "wire_bytes": len(blob) if wire_bytes is None else wire_bytes,
        "leaves": len(jax.tree.leaves(tree)),
        "leaves_placed": (len(jax.tree.leaves(tree))
                          if shardings is not None else 0),
        "overlap_ratio": 0.0, "streaming": 0.0,
    })
    if delta_hit is not None:
        _LAST_RESTORE["delta_hit"] = 1.0 if delta_hit else 0.0
    try:
        from kubetorch_tpu.observability.prometheus import (
            record_restore,
            record_wire,
        )

        record_restore(_LAST_RESTORE)
        record_wire({
            "rx_bytes": _LAST_RESTORE["wire_bytes"],
            "rx_raw_bytes": len(blob),
            "delta_fetch_hit": 1.0 if delta_hit else 0.0,
            "delta_fetch_miss": 1.0 if delta_hit is False else 0.0,
        })
    # ktlint: disable=KT004 -- metrics must never fail a restore
    except Exception:
        pass
    return tree
