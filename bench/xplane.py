"""Reader of the profiler's ``.xplane.pb`` (an XSpace protobuf) that keeps
what ``jax.profiler.ProfileData`` drops: each device operation's ``tf_op``
(the jit name-scope path), ``flops`` and ``bytes_accessed``, and the
``XLA Modules`` line that names each program run.

The XSpace schema (``tsl/profiler/protobuf/xplane.proto``) is declared here
field by field and parsed by the ``protobuf`` runtime, which comes with the
profiler's tools (``xprof``); the reader imports no ``tensorflow``, whose
runtime would load beside JAX's.

On top of it, the device time of a traced window by program part:

* ``scope_of(tf_op)``: the program's own ``jax.named_scope`` path in a
  ``tf_op``, JAX's transforms and the primitive left out, e.g.
  ``jit(paged_engine_step)/engine.decode/while/body/paged_decode_attn/dot``
  gives ``engine.decode/paged_decode_attn``;
* ``scope_times``: the device self-time of each scope, where an operation
  without a ``tf_op`` (an XLA-inserted copy, a ``while`` without metadata)
  takes the scope of the operation that encloses it, else the scope the
  operations nested in it share, else ``<module>|(unscoped)``;
* ``module_times``: the device time of each program, by its jit name.
"""
from __future__ import annotations

import collections
import re
from dataclasses import dataclass
from functools import lru_cache

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
UNSCOPED = "(unscoped)"
# components of a tf_op path that JAX writes itself; all else is a scope
_JAX_PARTS = re.compile(
    r"^(while|body|cond|branch_\d+_fun|closed_call|core_call|checkpoint|"
    r"remat|rematted_computation|scan|custom_jvp_call|custom_vjp_call|"
    r"pjit|jit|shard_map)$")
_PROGRAM = re.compile(r"^p?jit\(.*\)$")            # jit(f): the program
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")     # jvp(x), transpose(x)
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")           # jit_f(123) -> jit_f

_I64, _U64, _DBL, _STR, _MSG = 3, 4, 1, 9, 11
_ONEOF = {"XStat": ("value", {"double_value", "uint64_value", "int64_value",
                              "str_value", "ref_value"})}
# the fields read here; the parser skips the others
_SCHEMA = {   # message: [(field, number, type, repeated, message type)]
    "XSpace": [("planes", 1, _MSG, True, "XPlane")],
    "XPlane": [("name", 2, _STR, False, None),
               ("lines", 3, _MSG, True, "XLine"),
               ("event_metadata", 4, _MSG, True,
                "XPlane.EventMetadataEntry"),
               ("stat_metadata", 5, _MSG, True, "XPlane.StatMetadataEntry")],
    "XPlane.EventMetadataEntry": [("key", 1, _I64, False, None),
                                  ("value", 2, _MSG, False,
                                   "XEventMetadata")],
    "XPlane.StatMetadataEntry": [("key", 1, _I64, False, None),
                                 ("value", 2, _MSG, False, "XStatMetadata")],
    "XLine": [("name", 2, _STR, False, None),
              ("timestamp_ns", 3, _I64, False, None),
              ("events", 4, _MSG, True, "XEvent")],
    "XEvent": [("metadata_id", 1, _I64, False, None),
               ("offset_ps", 2, _I64, False, None),
               ("duration_ps", 3, _I64, False, None)],
    "XStat": [("metadata_id", 1, _I64, False, None),
              ("double_value", 2, _DBL, False, None),
              ("uint64_value", 3, _U64, False, None),
              ("int64_value", 4, _I64, False, None),
              ("str_value", 5, _STR, False, None),
              ("ref_value", 7, _U64, False, None)],
    "XEventMetadata": [("name", 2, _STR, False, None),
                       ("stats", 5, _MSG, True, "XStat")],
    "XStatMetadata": [("name", 2, _STR, False, None)],
}
_PKG = "bench_xplane"


@lru_cache(maxsize=1)
def _space_class():
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=_PKG, syntax="proto3")
    msgs = {}
    for full in _SCHEMA:
        outer, _, inner = full.partition(".")
        if inner:
            m = msgs[outer].nested_type.add(name=inner)
            m.options.map_entry = True
        else:
            m = msgs[outer] = f.message_type.add(name=outer)
        oneof, members = _ONEOF.get(full, ("", ()))
        if oneof:
            m.oneof_decl.add(name=oneof)
        for name, num, typ, rep, ref in _SCHEMA[full]:
            fd = m.field.add(name=name, number=num, type=typ, label=(
                3 if rep else 1))
            if ref:
                fd.type_name = f".{_PKG}.{ref}"
            if name in members:
                fd.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{_PKG}.XSpace"))


def read_space(path: str):
    """The file's XSpace message."""
    space = _space_class()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    return space


def _value(stat, stat_names: dict):
    """A stat's value; a ``ref_value`` names an interned string."""
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, kind) if kind else None


@dataclass
class Op:
    start_ns: float
    end_ns: float
    name: str
    tf_op: str
    module: str
    flops: int
    bytes_accessed: int


@dataclass
class Plane:
    name: str
    ops: list             # [Op], sorted by start
    modules: list         # [(start_ns, end_ns, module name)], sorted


def _events(line):
    base_ps = line.timestamp_ns * 1000
    for e in line.events:
        s = (base_ps + e.offset_ps) / 1000.0
        yield e, s, s + e.duration_ps / 1000.0


def module_name(event_name: str) -> str:
    """``jit_learner_step(1234)`` -> ``jit_learner_step``."""
    return _MODULE.match(event_name).group(1)


def device_planes(space) -> list:
    """Each device plane that ran operations, with its operations (their
    ``tf_op``, cost and the module whose run encloses them) and the
    intervals of its ``XLA Modules`` line."""
    out = []
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        smeta = {k: v.name for k, v in plane.stat_metadata.items()}
        emeta = {}
        for k, md in plane.event_metadata.items():
            st = {smeta.get(s.metadata_id, ""): _value(s, smeta)
                  for s in md.stats}
            emeta[k] = (md.name, str(st.get("tf_op") or ""),
                        int(st.get("flops") or 0),
                        int(st.get("bytes_accessed") or 0))
        ops, modules = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules += [(s, e, module_name(
                    plane.event_metadata[ev.metadata_id].name))
                    for ev, s, e in _events(line)]
            elif line.name == OPS_LINE:
                ops += [(s, e, emeta[ev.metadata_id])
                        for ev, s, e in _events(line)]
        if not ops:
            continue
        modules.sort()
        ops.sort(key=lambda o: (o[0], -o[1]))
        placed, k = [], 0
        for s, e, (name, tf_op, fl, by) in ops:
            while k < len(modules) and modules[k][1] <= s:
                k += 1
            mod = modules[k][2] if (k < len(modules)
                                    and modules[k][0] <= s) else ""
            placed.append(Op(s, e, name, tf_op, mod, fl, by))
        out.append(Plane(plane.name, placed, modules))
    return out


def host_spans(space, prefixes=("nat.", "bench.")) -> list:
    """(start_ns, end_ns, name) of the host annotations whose names start
    with one of ``prefixes``."""
    out = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            for ev, s, e in _events(line):
                n = names.get(ev.metadata_id, "")
                if n.startswith(prefixes):
                    out.append((s, e, n))
    return out


def scope_of(tf_op: str) -> str:
    """The program's named-scope path inside a ``tf_op`` ("" if none)."""
    parts = []
    for part in tf_op.rstrip(":").split("/")[:-1]:
        if _PROGRAM.match(part):
            continue
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        if part and not _JAX_PARTS.match(part):
            parts.append(part)
    return "/".join(parts)


def _self_times(ops, lo, hi):
    """Per op index: its time inside [lo, hi] less the time of the ops
    nested in it, and the index of the innermost op enclosing it (or
    None).  ``ops`` sorted by (start, -end), as ``Plane.ops``."""
    own, parent, stack = {}, {}, []
    for i, op in enumerate(ops):
        s, e = max(op.start_ns, lo), min(op.end_ns, hi)
        while stack and ops[stack[-1]].end_ns <= op.start_ns:
            stack.pop()
        parent[i] = stack[-1] if stack else None
        if e > s:
            if stack:
                j = stack[-1]
                own[j] = own.get(j, 0.0) - (min(e, ops[j].end_ns) - s)
            own[i] = own.get(i, 0.0) + (e - s)
        stack.append(i)
    return own, parent


def _common(paths) -> str:
    """The longest leading run of components the scope paths share."""
    split = [p.split("/") for p in paths]
    if not split:
        return ""
    out = []
    for parts in zip(*split):
        if any(x != parts[0] for x in parts):
            break
        out.append(parts[0])
    return "/".join(out)


def scope_times(plane: Plane, lo: float, hi: float) -> collections.Counter:
    """Device self-seconds of each scope in [lo, hi]: key
    ``<module>|<scope>``.  An operation without a ``tf_op`` (a copy XLA
    inserted, or a ``while`` the compiler emitted without metadata) takes
    the scope of the operation that encloses it, else the scope the
    operations nested in it share; failing both, ``<module>|(unscoped)``."""
    ops = plane.ops
    own, parent = _self_times(ops, lo, hi)
    kids = collections.defaultdict(list)
    for i, p in parent.items():
        if p is not None:
            kids[p].append(i)
    scope: dict = {}

    def of(i):
        if i not in scope:
            if ops[i].tf_op:
                s = scope_of(ops[i].tf_op)
            else:
                s = of(parent[i]) if parent[i] is not None else ""
                s = s or _common([scope_of(ops[k].tf_op) for k in kids[i]
                                  if ops[k].tf_op])
            scope[i] = s
        return scope[i]

    out = collections.Counter()
    for i, ns in own.items():
        out[f"{ops[i].module}|{of(i) or UNSCOPED}"] += ns / 1e9
    return out


def module_times(plane: Plane, lo: float, hi: float) -> collections.Counter:
    """Device seconds of each program's runs in [lo, hi], by jit name."""
    out = collections.Counter()
    for s, e, name in plane.modules:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[name] += (e - s) / 1e9
    return out


def under(times: dict, *scope: str, exclude: tuple = (),
          module: str = "") -> float:
    """Sum of ``scope_times`` entries whose scope path holds ``scope`` as
    consecutive components (e.g. ``("engine.decode",
    "paged_decode_attn")``) and none of ``exclude``; with ``module``, of
    that program's entries only."""
    total = 0.0
    n = len(scope)
    for key, sec in times.items():
        mod, path = key.split("|", 1)
        parts = path.split("/")
        if (module and mod != module) or any(x in parts for x in exclude):
            continue
        if any(parts[i:i + n] == list(scope)
               for i in range(len(parts) - n + 1)):
            total += sec
    return total
