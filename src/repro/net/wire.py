"""Binary wire format for the Farview network tier.

Every message is one length-prefixed frame (wire version 2):

      0      2      3      4             12           16        16+len
      +------+------+------+-------------+------------+=========+-----+
      | magic| ver  | type | request id  | payload len| payload | crc |
      | u16  | u8   | u8   | u64         | u32        | (tagged)| u32 |
      +------+------+------+-------------+------------+=========+-----+

`magic` (0x4656, "FV") and `ver` gate decoding up front: a garbage or
incompatible header raises the typed `ProtocolError` immediately instead
of a server mis-parsing bytes into a hang. `request id` correlates
responses to requests — a client may have thousands of verbs in flight
on one connection and responses return in completion order. `payload
len` is bounded by `MAX_PAYLOAD`, so an adversarial (or corrupt) length
field fails typed instead of OOM-ing the peer. `crc` (version 2, PR 9)
is a CRC32 over header + payload: a frame corrupted IN TRANSIT — the
chaos layer's bit flips, a flaky NIC — fails typed at the receiver
instead of silently delivering wrong bytes or misrouting a response
whose request id was the corrupted field. The magic/version checks
catch garbage; the checksum catches *plausible* garbage.

Deadlines ride SUBMIT payloads as a tagged `deadline_ms` field — the
REMAINING budget in milliseconds, not an absolute timestamp, so it
survives unsynchronized clocks. The server re-anchors it on its own
monotonic clock at admission and sheds expired work before dispatch
with a typed `DEADLINE_EXCEEDED` error frame (`E_DEADLINE`).

The payload is a tagged recursive value encoding (stdlib `struct`, no
pickle — the decoder only constructs types named in an explicit
registry):

    N/T/F  none / true / false          s/b  utf-8 string / raw bytes
    i      int64                        I    big int (two's complement)
    f      float64                      a    ndarray (dtype, shape, raw)
    t/l    tuple / list (count + items) d    dict (count + k,v pairs)
    D      registered dataclass (class name + field tuple)

The `D` registry covers exactly the operator IR (`Project` ... `Pack`),
`Column` and `FTable` — a pipeline travels the wire as the same frozen
dataclasses the scheduler coalesces on, so the server-side dispatch key
(and therefore PR 2 cross-client stacking) is identical to in-process.

Typed errors are first-class frames: `encode_error` maps the exception
class to a stable code and carries `node_id` / `op`, `decode_error`
rebuilds the SAME exception type client-side. That is what lets PR 6
failover (`NodeDeadError` → reroute, `DroppedDispatchError` → same-node
retry) work across a process boundary.
"""
from __future__ import annotations

import dataclasses
import struct
import zlib

import numpy as np

from repro.core import operators as op_ir
from repro.core.client import (DeadlineExceededError, FarviewError,
                               NodeDeadError)
from repro.core.table import Column, FTable
from repro.distributed.health import (DroppedDispatchError, OverloadedError,
                                      ReplicaUnavailableError)

MAGIC = 0x4656              # "FV"
VERSION = 2                 # v2: CRC32 trailer over header + payload
HEADER = struct.Struct(">HBBQI")
HEADER_SIZE = HEADER.size   # 16 bytes
TRAILER = struct.Struct(">I")
TRAILER_SIZE = TRAILER.size  # 4-byte CRC32 after the payload
MAX_PAYLOAD = 256 * 2**20   # a frame past this is a protocol error, not an OOM

# ------------------------------------------------------------------ frame types
HELLO = 0x01        # client -> server: {"version": int}
HELLO_OK = 0x02     # server -> client: {"version", "node_id", "n_regions"}
OPEN_QP = 0x10      # -> {} ; reply OK {"qp": vqp_id}
CLOSE_QP = 0x11     # -> {"qp"} ; reply OK {}
ALLOC = 0x12        # -> {"ft": FTable} ; reply OK {"table_id", "pages"}
FREE = 0x13         # -> {"table_id"} ; reply OK {}
REGISTER = 0x14     # -> {"name", "table_id"} ; reply OK {}  (catalog alias)
UNREGISTER = 0x15   # -> {"name"} ; reply OK {}
WRITE = 0x16        # -> {"table_id", "data": ndarray} ; reply OK {}
READ = 0x17         # -> {"table_id"} ; reply OK {"data"}
READ_ROWS = 0x18    # -> {"table_id", "idx"} ; reply OK {"data"}
SUBMIT = 0x20       # -> {"qp","table_id","pipeline",...} ; RESULT/ERROR later
FLUSH = 0x21        # -> {} ; reply OK {} once prior submits resolved
STATS = 0x22        # -> {} ; reply OK {pool counters, dispatches, queue depth}
OK = 0x40           # generic success reply (payload per request type)
RESULT = 0x41       # resolved SUBMIT: finalized PipelineResult payload
ERROR = 0x42        # typed failure: see encode_error / decode_error
OVERLOADED = 0x43   # admission shed: {"node_id", "detail"} — back off

FRAME_NAMES = {
    HELLO: "HELLO", HELLO_OK: "HELLO_OK", OPEN_QP: "OPEN_QP",
    CLOSE_QP: "CLOSE_QP", ALLOC: "ALLOC", FREE: "FREE",
    REGISTER: "REGISTER", UNREGISTER: "UNREGISTER", WRITE: "WRITE",
    READ: "READ", READ_ROWS: "READ_ROWS", SUBMIT: "SUBMIT",
    FLUSH: "FLUSH", STATS: "STATS", OK: "OK", RESULT: "RESULT",
    ERROR: "ERROR", OVERLOADED: "OVERLOADED",
}


class ProtocolError(FarviewError):
    """The byte stream is not a valid Farview frame (bad magic, wrong
    version, oversized length, truncated or malformed payload). The
    connection that produced it is poisoned — the peer drops it rather
    than guessing at a resync point — but other connections are
    unaffected and nothing hangs."""


# ------------------------------------------------------------- value encoding
# The `D` tag decodes ONLY classes in this registry (never arbitrary
# names): the operator IR the scheduler keys on, plus the table schema
# handles. All are plain dataclasses of primitives/tuples.
DATACLASS_REGISTRY = {
    cls.__name__: cls
    for cls in (op_ir.Project, op_ir.SmartAddress, op_ir.Predicate,
                op_ir.Select, op_ir.RegexMatch, op_ir.JoinSmall,
                op_ir.Distinct, op_ir.GroupBy, op_ir.Aggregate,
                op_ir.Crypt, op_ir.Pack,
                Column, FTable)
}

_I64 = struct.Struct(">q")
_F64 = struct.Struct(">d")
_U32 = struct.Struct(">I")


def _enc(obj, out: list) -> None:
    if obj is None:
        out.append(b"N")
    elif obj is True:
        out.append(b"T")
    elif obj is False:
        out.append(b"F")
    elif isinstance(obj, (np.integer, np.floating, np.bool_)):
        _enc(obj.item(), out)
    elif isinstance(obj, int):
        try:
            out.append(b"i" + _I64.pack(obj))
        except struct.error:        # past 64 bits: length-prefixed big int
            raw = obj.to_bytes((obj.bit_length() + 8) // 8, "big",
                               signed=True)
            out.append(b"I" + _U32.pack(len(raw)) + raw)
    elif isinstance(obj, float):
        out.append(b"f" + _F64.pack(obj))
    elif isinstance(obj, str):
        raw = obj.encode("utf-8")
        out.append(b"s" + _U32.pack(len(raw)) + raw)
    elif isinstance(obj, (bytes, bytearray, memoryview)):
        raw = bytes(obj)
        out.append(b"b" + _U32.pack(len(raw)) + raw)
    elif isinstance(obj, np.ndarray):
        # ascontiguousarray promotes 0-d to 1-d; only call it when needed
        arr = obj if obj.flags.c_contiguous else np.ascontiguousarray(obj)
        dt = arr.dtype.str.encode("ascii")
        out.append(b"a" + _U32.pack(len(dt)) + dt
                   + bytes([arr.ndim]))
        for dim in arr.shape:
            out.append(_U32.pack(dim))
        raw = arr.tobytes()
        out.append(_U32.pack(len(raw)) + raw)
    elif dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        name = type(obj).__name__
        if name not in DATACLASS_REGISTRY:
            raise TypeError(f"dataclass {name!r} is not wire-registered")
        fields = tuple(getattr(obj, f.name)
                       for f in dataclasses.fields(obj))
        raw = name.encode("ascii")
        out.append(b"D" + _U32.pack(len(raw)) + raw)
        _enc(fields, out)
    elif isinstance(obj, tuple):
        out.append(b"t" + _U32.pack(len(obj)))
        for item in obj:
            _enc(item, out)
    elif isinstance(obj, list):
        out.append(b"l" + _U32.pack(len(obj)))
        for item in obj:
            _enc(item, out)
    elif isinstance(obj, dict):
        out.append(b"d" + _U32.pack(len(obj)))
        for k, v in obj.items():
            _enc(k, out)
            _enc(v, out)
    else:
        # device arrays (jax) expose __array__; anything else is a bug
        arr = np.asarray(obj)
        if arr.dtype == object:
            raise TypeError(f"cannot wire-encode {type(obj).__name__}")
        _enc(arr, out)


def encode_value(obj) -> bytes:
    out: list = []
    _enc(obj, out)
    return b"".join(out)


class _Cursor:
    """Bounds-checked reader: every short read is a typed ProtocolError."""

    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes):
        self.buf = buf
        self.pos = 0

    def take(self, n: int) -> bytes:
        if n < 0 or self.pos + n > len(self.buf):
            raise ProtocolError(
                f"truncated payload: wanted {n} bytes at offset {self.pos}, "
                f"have {len(self.buf) - self.pos}")
        out = self.buf[self.pos:self.pos + n]
        self.pos += n
        return out

    def u32(self) -> int:
        return _U32.unpack(self.take(4))[0]


def _dec(cur: _Cursor):
    tag = cur.take(1)
    if tag == b"N":
        return None
    if tag == b"T":
        return True
    if tag == b"F":
        return False
    if tag == b"i":
        return _I64.unpack(cur.take(8))[0]
    if tag == b"I":
        return int.from_bytes(cur.take(cur.u32()), "big", signed=True)
    if tag == b"f":
        return _F64.unpack(cur.take(8))[0]
    if tag == b"s":
        try:
            return cur.take(cur.u32()).decode("utf-8")
        except UnicodeDecodeError as e:
            raise ProtocolError(f"malformed utf-8 string: {e}") from e
    if tag == b"b":
        return cur.take(cur.u32())
    if tag == b"a":
        try:
            dtype = np.dtype(cur.take(cur.u32()).decode("ascii"))
        except (TypeError, UnicodeDecodeError) as e:
            raise ProtocolError(f"bad ndarray dtype: {e}") from e
        ndim = cur.take(1)[0]
        shape = tuple(cur.u32() for _ in range(ndim))
        raw = cur.take(cur.u32())
        n_items = int(np.prod(shape, dtype=np.int64)) if shape else 1
        if dtype.itemsize * n_items != len(raw):
            raise ProtocolError(
                f"ndarray payload is {len(raw)} bytes, shape {shape} "
                f"of {dtype} needs {dtype.itemsize * n_items}")
        # copy out of the frame buffer so the array owns its memory
        return np.frombuffer(raw, dtype=dtype).reshape(shape).copy()
    if tag == b"D":
        name = cur.take(cur.u32()).decode("ascii", errors="replace")
        cls = DATACLASS_REGISTRY.get(name)
        if cls is None:
            raise ProtocolError(f"unknown wire dataclass {name!r}")
        fields = _dec(cur)
        if (not isinstance(fields, tuple)
                or len(fields) != len(dataclasses.fields(cls))):
            raise ProtocolError(f"bad field tuple for {name!r}")
        try:
            return cls(*fields)
        except (TypeError, ValueError) as e:
            raise ProtocolError(f"cannot rebuild {name!r}: {e}") from e
    if tag == b"t":
        return tuple(_dec(cur) for _ in range(cur.u32()))
    if tag == b"l":
        return [_dec(cur) for _ in range(cur.u32())]
    if tag == b"d":
        return {_dec(cur): _dec(cur) for _ in range(cur.u32())}
    raise ProtocolError(f"unknown value tag {tag!r}")


def decode_value(buf: bytes):
    cur = _Cursor(bytes(buf))
    try:
        obj = _dec(cur)
    except struct.error as e:       # short struct unpack inside a tag
        raise ProtocolError(f"malformed payload: {e}") from e
    if cur.pos != len(cur.buf):
        raise ProtocolError(
            f"{len(cur.buf) - cur.pos} trailing bytes after payload")
    return obj


# ------------------------------------------------------------------- framing
def encode_frame(ftype: int, req_id: int, obj=None, *,
                 max_payload: int = MAX_PAYLOAD) -> bytes:
    """One frame; `max_payload` is the bound the connection was set up
    with (the peer refuses anything larger)."""
    payload = b"" if obj is None else encode_value(obj)
    if len(payload) > max_payload:
        raise ProtocolError(
            f"payload of {len(payload)} bytes exceeds the "
            f"{max_payload}-byte bound")
    hdr = HEADER.pack(MAGIC, VERSION, ftype, req_id, len(payload))
    # CRC over header AND payload: a corrupted request id (misrouted
    # response) is as wrong as a corrupted byte in an ndarray
    return hdr + payload + TRAILER.pack(zlib.crc32(payload, zlib.crc32(hdr)))


def parse_header(hdr: bytes, *, max_payload: int = MAX_PAYLOAD):
    """-> (ftype, req_id, payload_len); typed errors for garbage."""
    if len(hdr) != HEADER_SIZE:
        raise ProtocolError(
            f"truncated header: {len(hdr)} of {HEADER_SIZE} bytes")
    magic, ver, ftype, req_id, length = HEADER.unpack(hdr)
    if magic != MAGIC:
        raise ProtocolError(f"bad magic 0x{magic:04x} (want 0x{MAGIC:04x})")
    if ver != VERSION:
        raise ProtocolError(f"unsupported wire version {ver} (speak "
                            f"{VERSION})")
    if ftype not in FRAME_NAMES:
        raise ProtocolError(f"unknown frame type 0x{ftype:02x}")
    if length > max_payload:
        raise ProtocolError(
            f"frame of {length} bytes exceeds the {max_payload}-byte bound")
    return ftype, req_id, length


def check_crc(hdr: bytes, payload: bytes, trailer: bytes) -> None:
    """Verify a received frame's CRC32 trailer; typed error on mismatch.
    Stream readers call this with the three byte ranges they just read —
    the only defense against bytes that are plausible but WRONG (a bit
    flip inside an ndarray payload parses fine and merges wrong)."""
    if len(trailer) != TRAILER_SIZE:
        raise ProtocolError(
            f"truncated crc trailer: {len(trailer)} of {TRAILER_SIZE} bytes")
    want = TRAILER.unpack(trailer)[0]
    got = zlib.crc32(payload, zlib.crc32(hdr))
    if got != want:
        raise ProtocolError(
            f"frame checksum mismatch (crc32 {got:#010x} != {want:#010x}): "
            "corrupted in transit")


def decode_frame(buf: bytes, *, max_payload: int = MAX_PAYLOAD):
    """Parse one COMPLETE frame from `buf` -> (ftype, req_id, payload obj).

    Test/bench convenience; the server and client read header + payload +
    crc trailer separately off their streams via `parse_header` +
    `check_crc` + `decode_value`."""
    ftype, req_id, length = parse_header(buf[:HEADER_SIZE],
                                         max_payload=max_payload)
    body = buf[HEADER_SIZE:HEADER_SIZE + length]
    trailer = buf[HEADER_SIZE + length:]
    if len(body) != length or len(trailer) != TRAILER_SIZE:
        raise ProtocolError(
            f"frame body is {len(buf) - HEADER_SIZE} bytes, header "
            f"promised {length} (+{TRAILER_SIZE} crc)")
    check_crc(buf[:HEADER_SIZE], body, trailer)
    return ftype, req_id, decode_value(body) if length else None


# -------------------------------------------------------------- typed errors
E_GENERIC = 1       # FarviewError (or any unclassified server failure)
E_NODE_DEAD = 2
E_DROPPED = 3
E_REPLICA = 4
E_OVERLOADED = 5
E_PROTOCOL = 6
E_MEMORY = 7        # pool out of pages — the client's alloc raises MemoryError
E_DEADLINE = 8      # budget spent before dispatch: the typed
#                     DEADLINE_EXCEEDED shed (never a health strike)

_ERROR_CODES = (
    # order matters: first isinstance match wins, subclasses before bases
    (E_NODE_DEAD, NodeDeadError),
    (E_DROPPED, DroppedDispatchError),
    (E_REPLICA, ReplicaUnavailableError),
    (E_OVERLOADED, OverloadedError),
    (E_DEADLINE, DeadlineExceededError),
    (E_PROTOCOL, ProtocolError),
    (E_GENERIC, FarviewError),
    (E_MEMORY, MemoryError),
)


def encode_error(exc: BaseException, *, node_id: int | None = None) -> dict:
    code = E_GENERIC
    for c, cls in _ERROR_CODES:
        if isinstance(exc, cls):
            code = c
            break
    return {"code": code, "msg": str(exc),
            "node_id": getattr(exc, "node_id", node_id),
            "op": getattr(exc, "op", None),
            "detail": getattr(exc, "detail", None)}


def decode_error(payload: dict) -> Exception:
    code = payload.get("code", E_GENERIC)
    msg = payload.get("msg", "remote error")
    node_id = payload.get("node_id")
    if code == E_NODE_DEAD:
        return NodeDeadError(int(node_id or 0),
                             op=payload.get("op") or "dispatch")
    if code == E_DROPPED:
        return DroppedDispatchError(int(node_id or 0))
    if code == E_REPLICA:
        return ReplicaUnavailableError(msg)
    if code == E_OVERLOADED:
        return OverloadedError(int(node_id or 0),
                               detail=payload.get("detail") or msg)
    if code == E_DEADLINE:
        return DeadlineExceededError(
            None if node_id is None else int(node_id),
            op=payload.get("op") or "dispatch",
            detail=payload.get("detail") or "deadline budget exhausted")
    if code == E_PROTOCOL:
        return ProtocolError(msg)
    if code == E_MEMORY:
        return MemoryError(msg)
    return FarviewError(msg)
