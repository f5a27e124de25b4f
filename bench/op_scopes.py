"""The named scope of each device op in a traced run, from the compiled
programs the profile itself carries.

A v5e op event is named by its HLO instruction (``%sort.5 = ...``) and
carries no scope.  The profile's ``/host:metadata`` plane does: it holds the
optimized HLO of every program the runtime knew while tracing, one
``Hlo Proto`` stat per program, under the program's module name
(``jit_counted(<program id>)``, as on the ``XLA Modules`` line).  Each
instruction there keeps the ``op_name`` JAX gave it, with every enclosing
``jax.named_scope`` in the path (``jit(counted)/decode/sample/...``), and
instruction names are unique within a module.  So an op's scope is the
``op_name`` of the instruction of its own name in the program it ran in.
A fusion carries the ``op_name`` of its root instruction.

``jax.profiler.ProfileData`` does not expose the metadata plane, so the
``.xplane.pb`` is read here at the protobuf wire level, field numbers as in
``tsl/profiler/protobuf/xplane.proto`` and ``xla/service/hlo.proto``.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

import serve_trace
import trace_reduce as tr

METADATA_PLANE = b"/host:metadata"
HLO_STAT = b"Hlo Proto"
# the engine jits every step through one wrapper, ``counted``, which opens
# the step's own name as a scope: op_name ``jit(counted)/<step>/...``
ENGINE_ROOT = "jit(counted)"
_ID = re.compile(r"\((\d+)\)$")


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for bytes, a string or a nested message."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, v


def _first(buf, num: int, default=None):
    for k, v in fields(buf):
        if k == num:
            return v
    return default


def _map_values(entries) -> Iterator[memoryview]:
    for e in entries:
        v = _first(e, 2)
        if v is not None:
            yield v


def program_id(module: str) -> Optional[str]:
    """``jit_counted(9017850881652161552)`` -> ``9017850881652161552``."""
    m = _ID.search(module)
    return m.group(1) if m else None


def hlo_protos(xspace: bytes) -> Dict[str, memoryview]:
    """Program id -> the serialized HloProto the metadata plane holds for
    it (XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5;
    XEventMetadata.name 2, stats 5; XStat.metadata_id 1, bytes_value 6)."""
    out: Dict[str, memoryview] = {}
    for num, plane in fields(xspace):
        if num != 1 or bytes(_first(plane, 2, b"")) != METADATA_PLANE:
            continue
        events, stat_names = [], {}
        for k, v in fields(plane):
            if k == 4:
                events.append(v)
            elif k == 5:
                for md in _map_values([v]):
                    stat_names[_first(md, 1, 0)] = bytes(_first(md, 2, b""))
        for md in _map_values(events):
            name = bytes(_first(md, 2, b"")).decode()
            for k, stat in fields(md):
                if k != 5 or stat_names.get(_first(stat, 1, 0)) != HLO_STAT:
                    continue
                proto = _first(stat, 6)
                pid = program_id(name)
                if proto is not None and pid is not None:
                    out[pid] = proto
    return out


def op_names(hlo_proto) -> Dict[str, str]:
    """Instruction name -> its ``op_name``, over every computation of the
    module (HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    metadata 7; OpMetadata.op_name 2)."""
    out: Dict[str, str] = {}
    module = _first(hlo_proto, 1, b"")
    for k, comp in fields(module):
        if k != 3:
            continue
        for j, ins in fields(comp):
            if j != 2:
                continue
            name, op = None, ""
            for f, v in fields(ins):
                if f == 1:
                    name = bytes(v).decode()
                elif f == 7:
                    op = bytes(_first(v, 2, b"")).decode()
            if name is not None:
                out[name] = op
    return out


def step_of(names: Dict[str, str]) -> Optional[str]:
    """The engine step a module compiles, from its ops' paths
    (``jit(counted)/<step>/...``): the most common such step, or None for a
    module that is not an engine step's."""
    seen: Dict[str, int] = {}
    for op in names.values():
        parts = op.split("/")
        if len(parts) > 1 and parts[0] == ENGINE_ROOT:
            seen[parts[1]] = seen.get(parts[1], 0) + 1
    return max(seen, key=seen.get) if seen else None


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is one of the path's named scopes."""
    return scope in op_name.split("/")


# --- ops of the traced window by scope -------------------------------------

# the engine's programs are modules ``jit_counted(<program id>)``
ENGINE_MODULES = "jit_counted("
# the scopes the model step opens, in the order a path is labelled by
SCOPES = ("sample", "head", "attention", "ssm", "mlp")


def read_protos(trace_dir: str) -> Dict[str, memoryview]:
    """`hlo_protos` of the profile ``trace_reduce.load`` reads."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        return hlo_protos(f.read())


class Programs:
    """Module name -> its instructions' op_names and its engine step,
    parsed on first use from `protos` (program id -> what `parse` takes)."""

    def __init__(self, protos, parse=op_names):
        self._protos, self._parse = protos, parse
        self._seen: Dict[str, tuple] = {}

    def __getitem__(self, module: str) -> Tuple[Optional[Dict[str, str]],
                                                Optional[str]]:
        pid = program_id(module)
        if pid not in self._seen:
            proto = self._protos.get(pid)
            names = None if proto is None else self._parse(proto)
            self._seen[pid] = (names, None if names is None
                               else step_of(names))
        return self._seen[pid]


def step_ops(dev, programs: Programs, step: str, lo: float,
             hi: float) -> List[Tuple[tr.Event, str]]:
    """The ops of one chip that start in [lo, hi) inside an execution of an
    engine program compiled for `step`, each with its op_name.  An engine
    program the profile holds no HLO for fails the run."""
    out = []
    for o, m in zip(dev["ops"], tr.op_program(dev["ops"], dev["modules"])):
        if m is None or not lo <= o.start < hi or not m.startswith(
                ENGINE_MODULES):
            continue
        names, owner = programs[m]
        if names is None:
            raise RuntimeError(f"the profile's metadata plane holds no HLO "
                               f"for {m}")
        if owner == step:
            out.append((o, names.get(tr.op_name(o), "")))
    return out


def label(op_name: str) -> str:
    for s in SCOPES:
        if in_scope(op_name, s):
            return s
    return "other"


def self_seconds(ops: List[Tuple[tr.Event, str]], lo: float,
                 hi: float) -> Dict[str, float]:
    """Device seconds by `label`, each instant put down to the innermost
    op running then (a layer's ops run nested inside the scan's ``while``
    op)."""
    events = [tr.Event(label(n), o.start, o.end, None) for o, n in ops]
    out: Dict[str, float] = {}
    for s, e, name in serve_trace.innermost(events, lo, hi):
        if name != serve_trace.OUTSIDE:
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out
