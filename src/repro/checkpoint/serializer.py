"""Checkpoint serialization: msgpack + zstd (+ optional int8 weight quant).

This is the TPU-side analogue of the paper's *bitstream compression* option
(DESIGN.md §3): compression shrinks the bytes moved during bring-up
("configuration phase") at the cost of extra decode compute — the same
trade-off Experiment 1 measures on the SPI link.  Three modes mirror the
paper's compression axis:

    none       raw little-endian tensors
    zstd       lossless zstd-3 (≈1.3-2× on bf16 weights)
    zstd+int8  blocked int8 quantization (kernels/dequant) + zstd
               (≈4× smaller; dequantize-on-load)

The format is mesh-agnostic: plain host numpy per leaf, keyed by pytree
path — restoring onto a different mesh/pod count (elastic re-mesh) is just
``device_put`` with the new sharding.

``deserialize`` returns a quantized leaf where the dequant kernel wrote it,
on the default device, and every other leaf as host numpy.  A ``zstd+int8``
restore therefore holds the whole dequantized tree on that one device.
"""
from __future__ import annotations

import io
import zlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
from jax.profiler import TraceAnnotation

try:  # optional: stdlib zlib is the fallback codec when zstandard is absent
    import zstandard
except ImportError:  # pragma: no cover - exercised on minimal containers
    zstandard = None

from repro.kernels.dequant import ops as dq

MODES = ("none", "zstd", "zstd+int8")
_QUANT_GROUP = 128

#: Compression backend actually used for the "zstd" modes.  ``zstandard`` is
#: an optional extra (see pyproject.toml); a clean container falls back to
#: stdlib zlib so checkpoints still round-trip (the blob records its codec).
HAVE_ZSTD = zstandard is not None


class _ZlibCompressor:
    def __init__(self, level: int = 6):
        self.level = level

    def compress(self, data: bytes) -> bytes:
        return zlib.compress(data, self.level)


class _ZlibDecompressor:
    def decompress(self, data: bytes) -> bytes:
        return zlib.decompress(data)


def _compressor(level: int):
    if HAVE_ZSTD:
        return "zstd", zstandard.ZstdCompressor(level=level)
    return "zlib", _ZlibCompressor()


def _decompressor(codec: str):
    if codec == "zstd":
        if not HAVE_ZSTD:
            raise ModuleNotFoundError(
                "checkpoint was written with the zstd codec but the "
                "'zstandard' package is not installed (pip install "
                "'repro[zstd]' or zstandard)"
            )
        return zstandard.ZstdDecompressor()
    if codec == "zlib":
        return _ZlibDecompressor()
    raise ValueError(f"unknown checkpoint codec {codec!r}")


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _should_quantize(path: str, arr: np.ndarray) -> bool:
    """int8-quantize large float matrices only (embeddings/projections);
    norms, biases and scalars stay exact."""
    return (
        arr.ndim >= 2
        and arr.dtype in (np.float32, np.dtype("bfloat16"))
        and arr.shape[-1] % _QUANT_GROUP == 0
        and arr.size >= 1 << 16
    )


def serialize(tree: Any, mode: str = "zstd", level: int = 3) -> bytes:
    """Pytree of arrays → bytes."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}")
    codec, cctx = _compressor(level)
    leaves = []
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        arr = np.asarray(jax.device_get(leaf))
        record: dict[str, Any] = {
            "path": _path_str(path),
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        }
        if mode == "zstd+int8" and _should_quantize(record["path"], arr):
            mat = arr.reshape(-1, arr.shape[-1])
            q, scales = dq.quantize_blocked(
                jnp.asarray(mat, jnp.float32), group=_QUANT_GROUP
            )
            record["quant"] = {
                "group": _QUANT_GROUP,
                "q": cctx.compress(np.asarray(q).tobytes()),
                "scales": cctx.compress(np.asarray(scales).tobytes()),
                "rows": int(mat.shape[0]),
            }
        else:
            raw = arr.tobytes()
            record["data"] = cctx.compress(raw) if mode != "none" else raw
        leaves.append(record)
    payload = {
        "version": 1,
        "mode": mode,
        "codec": codec,
        "leaves": leaves,
    }
    return msgpack.packb(payload, use_bin_type=True)


def deserialize(data: bytes, target: Any = None) -> Any:
    """bytes → pytree.  If ``target`` (a pytree of arrays/SDS with the same
    structure) is given, leaves are restored into its structure and cast to
    its dtypes; else a flat {path: array} dict is returned.  Quantized leaves
    are device arrays, the others host numpy."""
    with TraceAnnotation("checkpoint/unpack"):
        payload = msgpack.unpackb(data, raw=False)
    mode = payload["mode"]
    # blobs predating the codec field were always zstd-compressed
    dctx = _decompressor(payload.get("codec", "zstd")) if mode != "none" else None
    by_path: dict[str, Any] = {}
    for record in payload["leaves"]:
        shape = tuple(record["shape"])
        dtype = np.dtype(record["dtype"])
        if "quant" in record:
            qd = record["quant"]
            rows, group = qd["rows"], qd["group"]
            cols = int(np.prod(shape)) // rows
            with TraceAnnotation("checkpoint/decompress"):
                q_raw = dctx.decompress(qd["q"])
                scales_raw = dctx.decompress(qd["scales"])
            q = np.frombuffer(q_raw, np.int8).reshape(rows, cols)
            scales = np.frombuffer(scales_raw, np.float32).reshape(rows, cols // group)
            with TraceAnnotation("checkpoint/dequant"):
                mat = dq.dequantize(
                    jnp.asarray(q), jnp.asarray(scales), group=group,
                    dtype=jnp.dtype(dtype) if dtype != np.dtype("V2") else jnp.bfloat16,
                )
            arr = mat.reshape(shape)  # stays on the device
        elif mode == "none":
            arr = np.frombuffer(record["data"], dtype=dtype).reshape(shape)
        else:
            with TraceAnnotation("checkpoint/decompress"):
                raw = dctx.decompress(record["data"])
            arr = np.frombuffer(raw, dtype=dtype).reshape(shape)
        by_path[record["path"]] = arr
    if target is None:
        return by_path
    flat, treedef = jax.tree_util.tree_flatten_with_path(target)
    out = []
    for path, leaf in flat:
        key = _path_str(path)
        if key not in by_path:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = by_path[key]
        want = np.dtype(leaf.dtype)
        if arr.dtype != want:
            arr = arr.astype(want)
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def compression_stats(tree: Any) -> dict:
    """Bytes per mode — the 'Table 1' of the TPU configuration phase."""
    raw = sum(np.asarray(l).nbytes for l in jax.tree.leaves(tree))
    out = {"raw_bytes": raw}
    for mode in MODES:
        out[mode] = len(serialize(tree, mode))
    return out
