"""KV-slot migration payloads: the self-describing wire format that moves
one in-flight generation between replicas (ISSUE 15, disaggregated
prefill/decode serving).

A payload is everything another replica needs to continue a generation
bit-for-bit: the slot's *geometry* (block size, pool dtype, model KV
shape — validated against the importing engine before any block is
allocated), its *KV rows* (the referenced pool blocks, gathered per
block; int8 pools ship their per-block-per-head scale rows alongside),
and its *state machine* (prompt, prefill frontier for mid-prefill
migrations, or the full decode state — pending token, position, RNG key,
sampling knobs — for finished prefixes).  ``PagedEngine.export_slot``
builds one, ``PagedEngine.import_slot`` grafts one; this module owns the
host-side dict <-> bytes codec so the HTTP transport (``/kv/export`` ->
``/kv/import``), the router, and the in-process drain-evacuation path all
speak the same format.

**Wire form and at-rest form.**  On the wire a layer's K and V are
heads-major blocks, ``(n_blocks, kv_heads, block_size, d_head)``, and its
int8 scales ``(n_blocks, kv_heads)`` — the form every payload since PR 14
has had, which the version and the CRC cover.  On the device the pool
rests as block-major rows, ``(num_blocks, block_size, kv_heads * d_head)``
(`models/decode.init_kv_pool`: the layout its programs index, so that none
copies it).  ``export_slot`` / ``import_slot`` turn one slot's blocks from
the one into the other on the host; nothing in this module knows the
at-rest form, and a change of it is not a change of the wire.

The byte format is deliberately boring — magic + JSON header + raw
little-endian array bytes — so it is decodable with numpy alone (no
pickle, no jax): the router can size/forward payloads opaquely, and a
corrupted or truncated body fails loudly at the header/length checks
rather than grafting garbage KV.

Version 2 (``BPEKV002``, ISSUE 20) hardens the format for WAN-grade
links: the header carries a CRC32 over the (uncompressed) array section
and a codec flag — ``zstd`` when the extension is importable, ``zlib``
as the always-available stdlib fallback, ``raw`` otherwise — negotiated
per transfer via an accept list (the ``X-KV-Accept`` HTTP header on
``/kv/export``).  A bit-flipped or truncated body fails the CRC or
length check with ``ValueError`` — the transport maps that to a 400, so
a corrupt graft can never reach the worker.  Version-1 payloads still
decode (no CRC: best-effort legacy), so mixed-version fleets migrate
during a rolling deploy.
"""

from __future__ import annotations

import json
import zlib

import numpy as np

try:  # bfloat16 payload rows need the ml_dtypes numpy extension (jax
    # ships it); pure-numpy hosts still decode f32/int8 payloads fine.
    import ml_dtypes  # noqa: F401
except ImportError:
    pass

try:  # optional: the container may not ship python-zstandard; zlib is
    # the guaranteed stdlib fallback so negotiation always has a codec.
    import zstandard as _zstd  # type: ignore
except ImportError:
    _zstd = None

__all__ = [
    "PAYLOAD_MAGIC",
    "PAYLOAD_MAGIC_V1",
    "HAVE_ZSTD",
    "negotiate_codec",
    "supported_codecs",
    "payload_to_bytes",
    "payload_from_bytes",
    "payload_nbytes",
    "synthetic_decode_payload",
]

#: Format magic + version.  Bump the digits on any incompatible layout
#: change — import refuses unknown versions instead of misreading rows.
PAYLOAD_MAGIC = b"BPEKV002"
#: The PR 14 format: no CRC, no compression.  Still decoded (legacy).
PAYLOAD_MAGIC_V1 = b"BPEKV001"

HAVE_ZSTD = _zstd is not None

#: Codecs this host can encode/decode, best first.
_CODECS = (("zstd",) if HAVE_ZSTD else ()) + ("zlib", "raw")


def supported_codecs() -> tuple[str, ...]:
    """Codecs this host can decode, best first — what a replica
    advertises (statusz ``kv_accept``) and sends as ``X-KV-Accept``."""
    return _CODECS


def negotiate_codec(accept: str | None) -> str:
    """Pick the best locally available codec from a comma-separated accept
    list (e.g. the ``X-KV-Accept`` request header on ``/kv/export``).
    ``None``/empty means the peer predates negotiation — send ``raw`` so a
    v1-era importer is never handed a frame it cannot open."""
    if not accept:
        return "raw"
    offered = {tok.strip().lower() for tok in accept.split(",") if tok.strip()}
    for codec in _CODECS:
        if codec in offered:
            return codec
    return "raw"


def _compress(codec: str, data: bytes) -> bytes:
    if codec == "raw":
        return data
    if codec == "zlib":
        return zlib.compress(data, 1)
    if codec == "zstd":
        if _zstd is None:
            raise ValueError("zstd codec requested but zstandard not installed")
        return _zstd.ZstdCompressor(level=3).compress(data)
    raise ValueError(f"unknown KV payload codec {codec!r}")


def _decompress(codec: str, data: bytes, raw_nbytes: int) -> bytes:
    try:
        if codec == "raw":
            return data
        if codec == "zlib":
            return zlib.decompress(data)
        if codec == "zstd":
            if _zstd is None:
                raise ValueError(
                    "KV payload uses zstd but zstandard is not installed here"
                )
            return _zstd.ZstdDecompressor().decompress(
                data, max_output_size=raw_nbytes
            )
    except (zlib.error, MemoryError) as exc:
        raise ValueError(f"corrupt KV payload body ({codec}): {exc}") from None
    except Exception as exc:  # zstd errors are extension-specific types
        if codec == "zstd":
            raise ValueError(
                f"corrupt KV payload body (zstd): {exc}"
            ) from None
        raise
    raise ValueError(f"unknown KV payload codec {codec!r}")


def payload_to_bytes(payload: dict, *, codec: str = "raw") -> bytes:
    """Serialize an ``export_slot`` payload: magic, an 8-byte little-endian
    header length, the JSON header (meta + array manifest + codec +
    CRC32), then the array section — each array's raw bytes in manifest
    order, compressed as one frame when ``codec`` is not ``"raw"``."""
    meta = payload["meta"]
    manifest: list[dict] = []
    chunks: list[bytes] = []
    for i, layer in enumerate(payload["layers"]):
        for name in sorted(layer):
            arr = np.ascontiguousarray(layer[name])
            manifest.append(
                {
                    "key": f"L{i}/{name}",
                    "dtype": str(arr.dtype),
                    "shape": list(arr.shape),
                }
            )
            chunks.append(arr.tobytes())
    raw = b"".join(chunks)
    body = _compress(codec, raw)
    header = json.dumps(
        {
            "meta": meta,
            "arrays": manifest,
            "codec": codec,
            "crc32": zlib.crc32(raw) & 0xFFFFFFFF,
            "raw_nbytes": len(raw),
            "body_nbytes": len(body),
        },
        separators=(",", ":"),
    ).encode("utf-8")
    return b"".join(
        [PAYLOAD_MAGIC, len(header).to_bytes(8, "little"), header, body]
    )


def payload_from_bytes(data: bytes) -> dict:
    """Decode :func:`payload_to_bytes` output back into the payload dict.
    Accepts v2 (CRC-checked, optionally compressed) and legacy v1 frames.
    Raises ``ValueError`` on a bad magic, version, truncated body, CRC
    mismatch, or undecodable compression frame — loudly, so the transport
    can 400 instead of grafting garbage KV."""
    if not data.startswith(PAYLOAD_MAGIC[:5]):
        raise ValueError("not a KV migration payload (bad magic)")
    version_2 = data.startswith(PAYLOAD_MAGIC)
    if not version_2 and not data.startswith(PAYLOAD_MAGIC_V1):
        raise ValueError(
            f"unsupported KV payload version {data[:8]!r} "
            f"(expected {PAYLOAD_MAGIC!r} or {PAYLOAD_MAGIC_V1!r})"
        )
    off = len(PAYLOAD_MAGIC)
    if len(data) < off + 8:
        raise ValueError("truncated KV payload (no header length)")
    hlen = int.from_bytes(data[off: off + 8], "little")
    off += 8
    if len(data) < off + hlen:
        raise ValueError("truncated KV payload (header)")
    try:
        header = json.loads(data[off: off + hlen])
    except json.JSONDecodeError as exc:
        raise ValueError(f"corrupt KV payload header: {exc}") from None
    off += hlen
    meta = header["meta"]
    if version_2:
        codec = header.get("codec", "raw")
        body_nbytes = int(header.get("body_nbytes", len(data) - off))
        if len(data) < off + body_nbytes:
            raise ValueError(
                f"truncated KV payload (body: have {len(data) - off} of "
                f"{body_nbytes} bytes)"
            )
        raw = _decompress(
            codec, data[off: off + body_nbytes],
            int(header.get("raw_nbytes", 1 << 31)),
        )
        want_crc = int(header["crc32"]) & 0xFFFFFFFF
        got_crc = zlib.crc32(raw) & 0xFFFFFFFF
        if got_crc != want_crc:
            raise ValueError(
                f"KV payload CRC mismatch (header {want_crc:#010x}, "
                f"body {got_crc:#010x}) — refusing to graft corrupt KV"
            )
        section, sec_off = raw, 0
    else:
        section, sec_off = data, off
    layers: list[dict] = [{} for _ in range(int(meta["num_layers"]))]
    for spec in header["arrays"]:
        dtype = np.dtype(spec["dtype"])
        shape = tuple(int(d) for d in spec["shape"])
        nbytes = dtype.itemsize * int(np.prod(shape)) if shape else dtype.itemsize
        if len(section) < sec_off + nbytes:
            raise ValueError(
                f"truncated KV payload (array {spec['key']})"
            )
        arr = np.frombuffer(
            section, dtype=dtype, count=int(np.prod(shape)), offset=sec_off,
        ).reshape(shape)
        sec_off += nbytes
        layer_idx, name = spec["key"].split("/", 1)
        idx = int(layer_idx[1:])
        if not 0 <= idx < len(layers):
            raise ValueError(
                f"corrupt KV payload: array {spec['key']!r} names layer "
                f"{idx} of {len(layers)}"
            )
        layers[idx][name] = arr
    return {"meta": meta, "layers": layers}


def payload_nbytes(payload: dict) -> int:
    """Raw KV bytes a payload carries (rows + scales, header excluded) —
    the transfer-size gauge the migration telemetry reports."""
    return sum(
        int(np.asarray(arr).nbytes)
        for layer in payload["layers"]
        for arr in layer.values()
    )


def synthetic_decode_payload(
    config,
    *,
    block_size: int,
    kv_dtype: str,
    prompt_len: int = 8,
    max_new_tokens: int = 3,
    seed: int = 0,
) -> dict:
    """A zero-KV decode-state payload shaped for ``import_slot`` — what
    ``bpe-tpu warmup --role decode`` grafts so a decode-role node compiles
    its tick + import copy programs WITHOUT ever touching the chunk
    ladder (the rows are zeros; warmup only cares about program shapes).

    ``config`` is duck-typed (any object with ``num_layers`` /
    ``num_heads`` / ``num_kv_heads`` / ``d_head`` / ``context_length`` /
    ``activation_dtype``); ``kv_dtype`` is the pool label — ``"int8"`` or
    the activation dtype name, exactly as ``PagedEngine.kv_dtype``
    reports it.
    """
    kv_heads = config.num_kv_heads or config.num_heads
    span = min(prompt_len + max_new_tokens, config.context_length)
    n_blocks = -(-span // block_size)
    store = "int8" if kv_dtype == "int8" else kv_dtype
    layers = []
    wire_blocks = (n_blocks, kv_heads, block_size, config.d_head)
    for _ in range(config.num_layers):
        layer = {
            "k": np.zeros(wire_blocks, np.dtype(store)),
            "v": np.zeros(wire_blocks, np.dtype(store)),
        }
        if kv_dtype == "int8":
            layer["k_scale"] = np.zeros((n_blocks, kv_heads), np.float32)
            layer["v_scale"] = np.zeros((n_blocks, kv_heads), np.float32)
        layers.append(layer)
    prompt = [1] * prompt_len
    meta = {
        "format": 1,
        "block_size": block_size,
        "kv_dtype": kv_dtype,
        "num_layers": config.num_layers,
        "kv_heads": kv_heads,
        "d_head": config.d_head,
        "context_length": config.context_length,
        "n_blocks": n_blocks,
        "prompt": prompt,
        "prompt_len": prompt_len,
        "next_pos": prompt_len,
        "decoding": True,
        "generated": 1,
        "max_new_tokens": max_new_tokens,
        "stop_id": None,
        "seed": seed,
        "temperature": 0.0,
        "top_k": 0,
        "top_p": 2.0,
        "token": 1,
        "position": prompt_len,
        # PRNGKey(seed) for small seeds is [seed >> 32, seed & 0xffffffff].
        "key": [seed >> 32, seed & 0xFFFFFFFF],
        "request_id": None,
        "emitted": [1],
        "history": prompt + [1],
    }
    return {"meta": meta, "layers": layers}
