"""Serialization: cloudpickle envelope with zero-copy out-of-band buffers.

Capability counterpart of the reference's SerializationContext
(python/ray/_private/serialization.py): cloudpickle for arbitrary Python,
pickle protocol-5 out-of-band buffers so numpy / jax host arrays are written
into the shared-memory object store without an extra copy, and ObjectRef
capture hooks so refs nested inside values keep their identity (the borrowing
protocol hook point).

Wire layout of a serialized object:

    [8-byte header length][msgpack header][payload][buf0][buf1]...

header = {"pkl_len": int, "bufs": [int, ...], "refs": [hex, ...]}
"""

from __future__ import annotations

import pickle
import struct
from typing import Any, Callable

import cloudpickle
import msgpack
import numpy as np

_HEADER_FMT = "<Q"
_HEADER_LEN = struct.calcsize(_HEADER_FMT)

# Debug escape hatch: copy out-of-band buffers on deserialize instead of
# aliasing the source (shm mmap / message bytes).
import os as _os

_COPY_BUFFERS = _os.environ.get("RAY_TPU_COPY_DESER_BUFFERS", "") == "1"


class SerializedObject:
    """A serialized value plus its out-of-band buffers (not yet concatenated)."""

    __slots__ = ("header_bytes", "payload", "buffers", "contained_refs")

    def __init__(self, header_bytes: bytes, payload: bytes, buffers, contained_refs):
        self.header_bytes = header_bytes
        self.payload = payload
        self.buffers = buffers
        self.contained_refs = contained_refs

    @property
    def total_bytes(self) -> int:
        return (
            _HEADER_LEN
            + len(self.header_bytes)
            + len(self.payload)
            + sum(len(b) for b in self.buffers)
        )

    def write_into(self, view: memoryview) -> None:
        """Copy the object into a contiguous writable buffer (e.g. shm)."""
        off = 0
        view[off:off + _HEADER_LEN] = struct.pack(_HEADER_FMT, len(self.header_bytes))
        off += _HEADER_LEN
        view[off:off + len(self.header_bytes)] = self.header_bytes
        off += len(self.header_bytes)
        view[off:off + len(self.payload)] = self.payload
        off += len(self.payload)
        for b in self.buffers:
            n = len(b)
            view[off:off + n] = b.cast("B") if isinstance(b, memoryview) else memoryview(b).cast("B")
            off += n

    def to_bytes(self) -> bytes:
        out = bytearray(self.total_bytes)
        self.write_into(memoryview(out))
        return bytes(out)


def serialize(value: Any, ref_serializer: Callable | None = None) -> SerializedObject:
    """Serialize ``value``.

    ref_serializer(obj) -> hex string is invoked for every ObjectRef found
    inside the value so the owner can track borrowed references.

    Plain C-contiguous numpy arrays and bytes take a RAW fast path: the
    header describes the dtype/shape and the value's own buffer ships
    out-of-band, so the only copy the object ever sees is the single
    source->arena write in write_into (the create/seal in-place write
    the reference gets from plasma's C++ client).  cloudpickle costs
    ~100 us per call even for an ndarray — at put-microbench rates that
    was the single biggest line.
    """
    t = type(value)
    if t is np.ndarray and value.dtype.kind in "biufc" \
            and value.flags.c_contiguous:
        header = msgpack.packb({
            "pkl_len": 0, "bufs": [value.nbytes], "refs": [],
            "nd": [value.dtype.str, list(value.shape)],
        })
        return SerializedObject(header, b"", [memoryview(value).cast("B")],
                                [])
    if t is bytes:
        header = msgpack.packb({
            "pkl_len": 0, "bufs": [len(value)], "refs": [], "rawb": 1,
        })
        return SerializedObject(header, b"", [value], [])
    buffers: list[memoryview] = []

    def buffer_callback(buf):
        buffers.append(buf.raw())
        return False  # out-of-band

    # ObjectRef.__reduce__ appends every ref pickled inside ``value`` to the
    # thread-local capture list, so nested refs keep identity and the owner
    # can track borrows (the reference's out-of-band ObjectRef capture,
    # python/ray/_private/serialization.py).
    contained: list[str] = []
    from ray_tpu.core import object_ref as _orf

    token = _orf._push_capture_list(contained)
    try:
        payload = cloudpickle.dumps(value, protocol=5, buffer_callback=buffer_callback)
    finally:
        _orf._pop_capture_list(token)

    header = msgpack.packb(
        {
            "pkl_len": len(payload),
            "bufs": [len(b) for b in buffers],
            "refs": contained,
        }
    )
    return SerializedObject(header, payload, buffers, contained)


def deserialize(data, ref_deserializer: Callable | None = None) -> Any:
    """Deserialize from a contiguous buffer (bytes or memoryview).

    Buffers are reconstructed zero-copy as memoryviews into ``data`` — numpy
    arrays deserialized from shm alias the store segment until copied.
    """
    view = memoryview(data)
    (hlen,) = struct.unpack(_HEADER_FMT, view[:_HEADER_LEN])
    off = _HEADER_LEN
    header = msgpack.unpackb(view[off:off + hlen])
    off += hlen
    nd = header.get("nd")
    if nd is not None:
        # RAW ndarray fast path: reconstruct as a zero-copy view over
        # the buffer (aliasing shm until copied, same contract as the
        # pickle5 out-of-band path below).
        blen = header["bufs"][0]
        buf = bytes(view[off:off + blen]) if _COPY_BUFFERS \
            else view[off:off + blen]
        dtype, shape = np.dtype(nd[0]), tuple(nd[1])
        return np.frombuffer(buf, dtype=dtype).reshape(shape)
    if header.get("rawb"):
        blen = header["bufs"][0]
        return bytes(view[off:off + blen])
    payload = view[off:off + header["pkl_len"]]
    off += header["pkl_len"]
    bufs = []
    for blen in header["bufs"]:
        if _COPY_BUFFERS:
            bufs.append(pickle.PickleBuffer(bytes(view[off:off + blen])))
        else:
            bufs.append(pickle.PickleBuffer(view[off:off + blen]))
        off += blen
    from ray_tpu.core import object_ref as _orf

    token = _orf._push_ref_resolver(ref_deserializer)
    try:
        return pickle.loads(payload, buffers=bufs)
    finally:
        _orf._pop_ref_resolver(token)


def contained_refs(data) -> list[str]:
    view = memoryview(data)
    (hlen,) = struct.unpack(_HEADER_FMT, view[:_HEADER_LEN])
    header = msgpack.unpackb(view[_HEADER_LEN:_HEADER_LEN + hlen])
    return header.get("refs", [])
