"""Boundary wire codec: (de)serialize split-boundary activations in a
wire format decoupled from the storage dtype.

``encode_boundary`` turns a device array into the bytes a hop actually
ships: the raw storage bytes when the wire format equals the array's
dtype (bit-identical to the legacy serialization, so default runs don't
change), a cast payload for a narrower float wire, or -- for ``int8`` --
a two-part ``pack_frames`` buffer of (fp32 per-channel scales, int8
values) whose per-part crc32s let the transfer layer attribute corruption
to the scales frame vs the data frame.  ``decode_boundary`` inverts it
back to the storage dtype; a fault-free encode/decode is bit-identical to
``kernels.quant.boundary_roundtrip`` of the same array, which is what
makes ``apply_split(wire=...)`` the exact reference for a quantized
runtime run.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dtype_policy import policy_jnp_dtype
from repro.kernels.quant import (default_channel_axis, dequantize_boundary,
                                 quantize_boundary)
from repro.runtime import events as ev
from repro.runtime.transfer import pack_frames, unpack_frames

# Part labels for framed int8 payloads -- the chaos harness keys on these
# to count scales-frame vs data-frame corruption hits.
INT8_FRAME_LABELS = ("scales", "data")


@dataclasses.dataclass(frozen=True)
class BoundaryMeta:
    """Receiver-side description of one encoded boundary payload.

    Travels out of band: shape/dtype/axis are plan facts both endpoints
    already agree on, exactly like the legacy ``_serialize`` host-array
    handoff -- only the payload crosses the (faulty) link."""

    wire: str                    # concrete wire format of the payload
    storage: np.dtype            # dtype decode restores
    shape: tuple[int, ...]
    axis: int | None = None      # int8 scale-group axis (None = per-tensor)
    framed: tuple[str, ...] | None = None  # pack_frames labels (int8 only)
    raw_bytes: int = 0           # storage-dtype serialized size (stats)


def queue_boundary(arr, wire: str, *, backend: str | None = None
                   ) -> tuple[tuple, BoundaryMeta]:
    """The device half of ``encode_boundary``: queue the cast or the int8
    quantize, then each result's copy to the host, and wait for none of
    it.  Returns ``(arrays, meta)`` for ``finish_boundary``.  A caller can
    queue more device work between the two halves without putting it
    ahead of what the hop's wait covers; it opens the ``wire.encode`` span
    around both halves itself, as ``encode_boundary`` does."""
    storage = np.dtype(arr.dtype)
    shape = tuple(int(d) for d in arr.shape)
    raw_bytes = int(arr.size) * storage.itemsize
    if wire == "int8":
        axis = default_channel_axis(arr.ndim)
        arrays = quantize_boundary(arr, axis, backend=backend)
        meta = BoundaryMeta(wire=wire, storage=storage, shape=shape,
                            axis=axis, framed=INT8_FRAME_LABELS,
                            raw_bytes=raw_bytes)
    else:
        jdt = policy_jnp_dtype(wire)
        arrays = (arr if arr.dtype == jdt else arr.astype(jdt),)
        meta = BoundaryMeta(wire=wire, storage=storage, shape=shape,
                            raw_bytes=raw_bytes)
    for a in arrays:
        a.copy_to_host_async()
    return tuple(arrays), meta


def finish_boundary(arrays: tuple, meta: BoundaryMeta
                    ) -> tuple[bytes, BoundaryMeta]:
    """The host half of ``encode_boundary``, for what ``queue_boundary``
    queued: wait for the device work that makes ``arrays`` inside the span
    ``wire.sync`` (a trace then tells the stage's device time apart from
    the codec's host time), then make the payload bytes.  The copies were
    queued first, so the wait adds no round trip to the hop."""
    with jax.profiler.TraceAnnotation(ev.SPAN_WIRE_SYNC):
        jax.block_until_ready(arrays)
    if meta.framed:
        q, scales = arrays
        q_host = np.ascontiguousarray(np.asarray(q))
        s_host = np.ascontiguousarray(np.asarray(scales, dtype=np.float32))
        return pack_frames(s_host.tobytes(), q_host.tobytes()), meta
    return np.ascontiguousarray(np.asarray(arrays[0])).tobytes(), meta


@ev.spanned(ev.SPAN_WIRE_ENCODE)
def encode_boundary(arr, wire: str, *, backend: str | None = None
                    ) -> tuple[bytes, BoundaryMeta]:
    """Encode ``arr`` for the wire; returns ``(payload, meta)``.

    ``wire`` must be concrete (``fp32``/``bf16``/``int8``) -- resolve
    ``follow`` with ``core.dtype_policy.resolve_wire_dtype`` first.  When
    the wire format equals the array's dtype the payload is bit-identical
    to ``np.asarray(arr).tobytes()`` (the legacy raw path)."""
    return finish_boundary(*queue_boundary(arr, wire, backend=backend))


@ev.spanned(ev.SPAN_WIRE_DECODE)
def decode_boundary(payload: bytes, meta: BoundaryMeta, *,
                    backend: str | None = None) -> jnp.ndarray:
    """Invert ``encode_boundary`` back to a device array in the storage
    dtype.  Decoding an uncorrupted payload reproduces
    ``boundary_roundtrip(arr, meta.wire)`` bit-for-bit."""
    if meta.wire == "int8":
        s_b, q_b = unpack_frames(payload, meta.framed or INT8_FRAME_LABELS)
        q = jnp.asarray(np.frombuffer(q_b, np.int8).reshape(meta.shape))
        scales = jnp.asarray(np.frombuffer(s_b, np.float32))
        return dequantize_boundary(q, scales, meta.axis,
                                   out_dtype=meta.storage, backend=backend)
    wdt = policy_jnp_dtype(meta.wire)
    x = jnp.asarray(np.frombuffer(payload, dtype=wdt).reshape(meta.shape))
    return x if x.dtype == meta.storage else x.astype(meta.storage)
