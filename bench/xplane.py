"""Read a profiler capture with every stat, and reduce what the program's
own names say: named scopes on device ops, host annotations.

``jax.profiler.ProfileData`` yields each event's own stats only.  An
op's named-scope path is either a stat of the op's *event metadata*
(``tf_op``) or only in the program's HLO, which the capture keeps in
its ``/host:metadata`` plane (one ``Hlo Proto`` per program id; each
instruction's ``metadata.op_name``).  So this module reads the
``.xplane.pb`` itself, with small hand-written descriptors (field
numbers of ``tsl/profiler/protobuf/xplane.proto`` and of the few
``xla/service/hlo.proto`` fields it needs) and ``google.protobuf``.  It
reads the binary capture and the text form the test fixtures keep.

What it reduces (every function returns None, never a guess, where the
capture lacks the names):

* ``phase_ns``: device time of the window programs' innermost ops by
  phase -- the first of the scopes ``sample``, ``validate``, ``score`` on
  the op's path (``core/sampler.py``, ``core/validate.py``,
  ``core/engine.py``), else ``other``;
* ``dispatched_samples``: the ``samples`` stat summed over the
  ``engine.dispatch`` host annotations;
* ``window_gaps_ns``: the device idle between consecutive window
  executions whose gap lies inside one ``session.drain`` annotation;
* ``allreduce_ns``: device time of the all-reduce ops inside the window
  executions, per execution per device (a mesh's ``psum``).
"""
from __future__ import annotations

import os
import re

from bench import trace as tracing

PHASES = ("sample", "validate", "score")
#: op stats that may hold the named-scope path, in order of preference
#: (``op_name`` is filled in from the program's HLO where the op has no
#: ``tf_op``)
SCOPE_STATS = ("tf_op", "op_name")
METADATA_PLANE = "/host:metadata"
#: HLO opcodes of an all-reduce, synchronous or split in two
ALLREDUCE_OPCODES = ("all-reduce", "all-reduce-start", "all-reduce-done")

_CLASSES: dict = {}
_MEMO: dict = {}


def _messages() -> dict:
    """XSpace and its parts as protobuf message classes."""
    if _CLASSES:
        return _CLASSES
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, DBL = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    STR, BYT, MSG = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    ONE, REP = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def message(name, fields, nested=(), oneof=None):
        m = fdp.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, num, ftype, label, tname, in_oneof in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = ".bench_xplane." + tname
            if in_oneof:
                f.oneof_index = 0
        for nname, vtype in nested:
            e = m.nested_type.add(name=nname)
            e.options.map_entry = True
            e.field.add(name="key", number=1, type=I64, label=ONE)
            e.field.add(name="value", number=2, type=MSG, label=ONE,
                        type_name=".bench_xplane." + vtype)
        return m

    message("XSpace", [("planes", 1, MSG, REP, "XPlane", False),
                       ("errors", 2, STR, REP, "", False),
                       ("warnings", 3, STR, REP, "", False),
                       ("hostnames", 4, STR, REP, "", False)])
    message("XPlane", [
        ("id", 1, I64, ONE, "", False), ("name", 2, STR, ONE, "", False),
        ("lines", 3, MSG, REP, "XLine", False),
        ("event_metadata", 4, MSG, REP, "XPlane.EventMetadataEntry", False),
        ("stat_metadata", 5, MSG, REP, "XPlane.StatMetadataEntry", False),
        ("stats", 6, MSG, REP, "XStat", False)],
        nested=(("EventMetadataEntry", "XEventMetadata"),
                ("StatMetadataEntry", "XStatMetadata")))
    message("XLine", [
        ("id", 1, I64, ONE, "", False),
        ("display_id", 10, I64, ONE, "", False),
        ("name", 2, STR, ONE, "", False),
        ("display_name", 11, STR, ONE, "", False),
        ("timestamp_ns", 3, I64, ONE, "", False),
        ("duration_ps", 9, I64, ONE, "", False),
        ("events", 4, MSG, REP, "XEvent", False)])
    message("XEvent", [
        ("metadata_id", 1, I64, ONE, "", False),
        ("offset_ps", 2, I64, ONE, "", True),
        ("num_occurrences", 5, I64, ONE, "", True),
        ("duration_ps", 3, I64, ONE, "", False),
        ("stats", 4, MSG, REP, "XStat", False)], oneof="data")
    message("XStat", [
        ("metadata_id", 1, I64, ONE, "", False),
        ("double_value", 2, DBL, ONE, "", True),
        ("uint64_value", 3, U64, ONE, "", True),
        ("int64_value", 4, I64, ONE, "", True),
        ("str_value", 5, STR, ONE, "", True),
        ("bytes_value", 6, BYT, ONE, "", True),
        ("ref_value", 7, U64, ONE, "", True)], oneof="value")
    message("XEventMetadata", [
        ("id", 1, I64, ONE, "", False), ("name", 2, STR, ONE, "", False),
        ("display_name", 4, STR, ONE, "", False),
        ("metadata", 3, BYT, ONE, "", False),
        ("stats", 5, MSG, REP, "XStat", False),
        ("child_id", 6, I64, REP, "", False)])
    message("XStatMetadata", [
        ("id", 1, I64, ONE, "", False), ("name", 2, STR, ONE, "", False),
        ("description", 3, STR, ONE, "", False)])
    # the HLO a capture keeps per program: only the fields read here
    message("HloProto", [("hlo_module", 1, MSG, ONE, "HloModuleProto",
                          False)])
    message("HloModuleProto", [
        ("name", 1, STR, ONE, "", False),
        ("computations", 3, MSG, REP, "HloComputationProto", False)])
    message("HloComputationProto", [
        ("name", 1, STR, ONE, "", False),
        ("instructions", 2, MSG, REP, "HloInstructionProto", False)])
    message("HloInstructionProto", [
        ("name", 1, STR, ONE, "", False), ("opcode", 2, STR, ONE, "", False),
        ("metadata", 7, MSG, ONE, "OpMetadata", False)])
    message("OpMetadata", [("op_type", 1, STR, ONE, "", False),
                           ("op_name", 2, STR, ONE, "", False)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    for name in ("XSpace", "XPlane", "XLine", "XEvent", "XStat",
                 "XEventMetadata", "XStatMetadata", "HloProto"):
        _CLASSES[name] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("bench_xplane." + name))
    return _CLASSES


def load_space(path: str):
    """The XSpace message of a binary ``.xplane.pb`` or its text form."""
    space = _messages()["XSpace"]()
    if path.endswith(".pbtxt"):
        from google.protobuf import text_format
        with open(path) as f:
            text_format.Parse(f.read(), space)
    else:
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
    return space


def _stat_value(stat, names: dict):
    kind = stat.WhichOneof("value")
    if kind is None:
        return None
    v = getattr(stat, kind)
    return names.get(v, v) if kind == "ref_value" else v


def hlo_op_names(space) -> dict:
    """``{program id: {instruction name: op_name}}`` from the HLO the
    capture keeps in its metadata plane."""
    out: dict = {}
    hlo_cls = _messages()["HloProto"]
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        for pid, md in plane.event_metadata.items():
            for stat in md.stats:
                if stat.WhichOneof("value") != "bytes_value":
                    continue
                hlo = hlo_cls.FromString(stat.bytes_value)
                out[int(pid)] = {
                    ins.name: ins.metadata.op_name
                    for comp in hlo.hlo_module.computations
                    for ins in comp.instructions if ins.metadata.op_name}
    return out


def _instruction(name: str, stats: dict) -> str:
    """An op event's HLO instruction name (``hlo_op``, else its event
    name, which a TPU writes as the instruction's text)."""
    return str(stats.get("hlo_op") or tracing.op_name(name)).lstrip("%")


def load_planes(path: str) -> list:
    """``[(plane name, {line name: [(name, start_ns, end_ns, stats)]})]``;
    ``stats`` joins the event metadata's stats and the event's own, and
    a device op without ``tf_op`` gets the ``op_name`` of its instruction
    in the program's HLO."""
    out = []
    space = load_space(path)
    hlo = hlo_op_names(space)
    for plane in space.planes:
        device = tracing.is_device(plane.name)
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for k, md in plane.event_metadata.items():
            meta[k] = (md.name, {names.get(s.metadata_id, str(s.metadata_id)):
                                 _stat_value(s, names) for s in md.stats})
        lines: dict = {}
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                name, mstats = meta.get(ev.metadata_id, ("", {}))
                stats = dict(mstats)
                for s in ev.stats:
                    stats[names.get(s.metadata_id, str(s.metadata_id))] = \
                        _stat_value(s, names)
                if device and "tf_op" not in stats and "program_id" in stats:
                    op = hlo.get(int(stats["program_id"]), {}).get(
                        _instruction(name, stats))
                    if op:
                        stats["op_name"] = op
                t0 = line.timestamp_ns + ev.offset_ps / 1e3
                evs.append((name, t0, t0 + ev.duration_ps / 1e3, stats))
        out.append((plane.name, lines))
    return out


def capture(ctx, profile_dir: str):
    """The planes of the run's capture, read once per file: the file at
    ``ctx.xplane`` where the caller names one, else the newest under
    ``profile_dir``; None where there is none."""
    path = getattr(ctx, "xplane", None) or tracing.find_xplane(profile_dir)
    if not path or not os.path.exists(path):
        return None
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        _MEMO.clear()
        _MEMO[key] = load_planes(path)
    return _MEMO[key]


def _devices(planes: list) -> list:
    return [ls for n, ls in planes if tracing.is_device(n)]


def _windows(lines: dict) -> list:
    """``(start, end)`` of the window program's executions, in order."""
    return sorted((s, e) for name, s, e, _ in
                  lines.get(tracing.MODULES_LINE, []) if "window" in name)


def phase_of(path: str) -> str:
    """The first of ``PHASES`` among the path's components (a TPU writes
    ``tf_op`` as ``path:type``)."""
    for part in path.split("/"):
        part = part.split(":", 1)[0]
        if part in PHASES:
            return part
    return "other"


def phase_ns(planes: list) -> dict | None:
    """Device ns of the window executions' innermost ops by phase, summed
    over devices; None without a window execution or without a scoped
    op."""
    out = {p: 0.0 for p in PHASES + ("other",)}
    seen = False
    for lines in _devices(planes):
        wins = _windows(lines)
        if not wins:
            continue
        seen = True
        ops = [(n, s, e, st) for n, s, e, st in
               lines.get(tracing.OPS_LINE, [])
               if any(a <= s and e <= b for a, b in wins)]
        stats = {(n, s, e): st for n, s, e, st in ops}
        for n, s, e in tracing.innermost([(n, s, e) for n, s, e, _ in ops]):
            st = stats[(n, s, e)]
            path = next((str(st[k]) for k in SCOPE_STATS if st.get(k)), "")
            out[phase_of(path)] += e - s
    if not seen or not any(out[p] for p in PHASES):
        return None
    return out


def dispatched_samples(planes: list) -> int | None:
    """Samples on the captured ``engine.dispatch`` annotations."""
    total = 0
    for name, ls in planes:
        if tracing.is_device(name):
            continue
        for evs in ls.values():
            total += sum(int(st.get("samples", 0)) for n, _, _, st in evs
                         if n == "engine.dispatch")
    return total or None


def ns_per_sample(ctx, profile_dir: str, phases: tuple) -> float | None:
    """Device ns of the innermost ops under ``phases`` in the captured
    window executions over the samples on the captured
    ``engine.dispatch`` annotations; None where the capture has no such
    scope or annotation."""
    planes = capture(ctx, profile_dir)
    if planes is None:
        return None
    ns, samples = phase_ns(planes), dispatched_samples(planes)
    if not ns or not samples or not sum(ns[p] for p in phases):
        return None
    return sum(ns[p] for p in phases) / samples


def _host_events(planes: list, wanted: str) -> list:
    return [(s, e) for n, ls in planes if not tracing.is_device(n)
            for evs in ls.values() for name, s, e, _ in evs
            if name == wanted]


def window_gaps_ns(planes: list) -> list | None:
    """Idle ns between consecutive window executions on the first device
    whose gap lies inside one ``session.drain`` annotation: the gap less
    any other program the device ran in it.  None without such a
    pair."""
    devices = _devices(planes)
    drains = _host_events(planes, "session.drain")
    if not devices or not drains:
        return None
    lines = devices[0]
    wins = _windows(lines)
    busy = tracing.merge([(s, e) for _, s, e, _ in
                          lines.get(tracing.MODULES_LINE, [])])
    gaps = []
    for (_, e0), (s1, _) in zip(wins[:-1], wins[1:]):
        # the gap, not the windows: the device clock sits a fraction of a
        # millisecond off the host's, so a drain's first window can start
        # before the drain's annotation does
        if not any(a <= e0 and s1 <= b for a, b in drains):
            continue
        ran = sum(min(e, s1) - max(s, e0) for s, e in busy
                  if s < s1 and e > e0)
        gaps.append(max(0.0, (s1 - e0) - ran))
    return gaps or None


def opcode(name: str) -> str:
    """An op event's HLO opcode, from its name: the instruction's text
    as a TPU writes it (``%x = s64[2]{0} all-reduce(...)``: the first
    lower-case word before a parenthesis after the ``=``), else the bare
    instruction name less its number (``%all-reduce.3``)."""
    if " = " in name:
        m = re.search(r"(?<![\w-])([a-z][a-z0-9-]*)\(",
                      name.split(" = ", 1)[1])
        if m:
            return m.group(1)
    return re.sub(r"\.\d+$", "", tracing.op_name(name).lstrip("%"))


def allreduce_ns(planes: list) -> float | None:
    """Mean device ns, per window execution per device, of the innermost
    ops with an all-reduce opcode (``ALLREDUCE_OPCODES``) inside the
    window executions; None where there is none."""
    total, runs, seen = 0.0, 0, False
    for lines in _devices(planes):
        wins = _windows(lines)
        runs += len(wins)
        ops = [(n, s, e) for n, s, e, _ in lines.get(tracing.OPS_LINE, [])
               if any(a <= s and e <= b for a, b in wins)]
        for n, s, e in tracing.innermost(ops):
            if opcode(n) in ALLREDUCE_OPCODES:
                seen = True
                total += e - s
    return total / runs if seen else None
