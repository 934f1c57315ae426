"""Reading a `jax.profiler` trace: the program's spans and the epoch
program's layer scopes, per chip, on the profiler's clock.

    python scripts/profview.py PROFILE_DIR [--window NAME] [--chips N]

`reduce_file` keeps, from one `*.xplane.pb`:

    t0, t1   the window: the first host event called `window` where one
             is named, else the extent of the device's operations (of
             the spans, on a host without a TPU)
    devices  per TPU chip, the operations of its "XLA Ops" line, less
             the containers (while, conditional, call) that hold other
             operations: [start, duration, op text]
    scopes   per chip, the operations inside the window whose `op_name`
             path holds one of the five layer scopes: [start, duration,
             scope]
    spans    the host events named `sagips.*` (the program's
             `obs.trace.span`s and `train_vmap`'s annotations):
             [start, duration, name, thread, args], where thread is the
             host line's index and args the span's stats

Times are nanoseconds.  A TPU trace's op events carry no `op_name`: the
profiler keeps each program's optimised HLO (an `HloProto`) in the
`/host:metadata` plane, keyed by program id, and a device plane's event
metadata names the program and the instruction of each op.  The trace's
Python API reaches neither, so `op_scope_table` reads the protobuf wire
format, only as deep as these fields of tsl's xplane.proto and xla's
hlo.proto go.

`summary` turns the reduced trace into what the layers cost: device time
per scope (the union of the scope's op intervals in the window, averaged
over chips), the share left unscoped and its largest ops, and for the
solve service each `sagips.solve.step` child's mean per batch and the
mean queue wait per request.
"""
from __future__ import annotations

import glob
import os
import re

__all__ = ["SCOPES", "STEP_CHILDREN", "batches", "find_trace",
           "innermost_scope", "op_scope_table", "reduce_file",
           "scope_seconds", "summary"]

# the epoch program's layers, one `jax.named_scope` each
SCOPES = ("sagips_sample", "sagips_gen", "sagips_disc", "sagips_exchange",
          "sagips_apply")
_SCOPE = re.compile(r"sagips_(?:sample|gen|disc|exchange|apply)(?![\w])")
# the children of one `sagips.solve.step` (serving/service.py)
STEP_CHILDREN = ("sagips.solve.drain", "sagips.solve.compile",
                 "sagips.solve.assemble", "sagips.solve.dispatch",
                 "sagips.solve.fetch", "sagips.solve.resolve")
_CONTAINERS = ("while", "conditional", "call")


def innermost_scope(path: str):
    """The innermost of the five scopes on an `op_name` path such as
    `jit(f)/while/body/transpose(jvp(vmap(sagips_disc)))/dot_general`,
    or None."""
    found = _SCOPE.findall(path)
    return found[-1] if found else None


def _opcode(text: str) -> str:
    """The HLO opcode of an op's text `%name = <shape> opcode(...)...`."""
    rest = text.split(" = ", 1)[1] if " = " in text else text
    if rest.startswith("("):                 # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    elif " " in rest:
        rest = rest.split(" ", 1)[1]
    return rest.strip().split("(", 1)[0]


# ---------------------------------------------------------------------------
# an operation's `op_name`, from the trace file itself

_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_MD, _XPLANE_STAT_MD = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_MD_NAME, _EVENT_MD_DISPLAY, _EVENT_MD_STATS = 2, 4, 5
_STAT_MD_ID, _STAT_MD_NAME = 1, 2
_STAT_ID, _STAT_U64, _STAT_I64, _STAT_BYTES = 1, 3, 4, 6
_HLO_MODULE, _MODULE_COMPUTATIONS = 1, 3
_COMP_INSTRUCTIONS, _COMP_ID = 2, 5
_INSTR_NAME, _INSTR_METADATA, _INSTR_CALLS = 1, 7, 38
_METADATA_OP_NAME = 2


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    bytes for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _ints(value) -> list:
    """A repeated integer field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _program_scopes(hlo_proto) -> dict:
    """Instruction name -> scope, for one program.  A fusion whose own
    `op_name` holds no scope (XLA made it, e.g. to pack a mask) takes the
    scope that most of the instructions it fuses carry."""
    module = dict(_fields(hlo_proto)).get(_HLO_MODULE, b"")
    comps, instrs = {}, []
    for f, comp in _fields(module):
        if f != _MODULE_COMPUTATIONS:
            continue
        cid, body = 0, []
        for g, v in _fields(comp):
            if g == _COMP_ID:
                cid = v
            elif g == _COMP_INSTRUCTIONS:
                name, path, calls = "", "", []
                for h, w in _fields(v):
                    if h == _INSTR_NAME:
                        name = _text(w)
                    elif h == _INSTR_METADATA:
                        path = _text(dict(_fields(w)).get(_METADATA_OP_NAME,
                                                          b""))
                    elif h == _INSTR_CALLS:
                        calls += _ints(w)
                body.append((name, innermost_scope(path), calls))
        comps[cid] = body
        instrs += body
    out = {}
    for name, scope, calls in instrs:
        if scope is None and calls:
            votes = {}
            for c in calls:
                for _, s, _ in comps.get(c, ()):
                    if s:
                        votes[s] = votes.get(s, 0) + 1
            scope = max(votes, key=votes.get) if votes else None
        if scope:
            out[name] = scope
    return out


def op_scope_table(path: str) -> dict:
    """{device plane name: {op text (the event's name): scope}} for the
    device ops of the trace file at `path` whose instruction holds one of
    the five scopes."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for field, value in _fields(space):
        if field == _XSPACE_PLANES:
            raw = list(_fields(value))
            name = next((_text(v) for f, v in raw if f == _XPLANE_NAME), "")
            planes.append((name, raw))

    def map_entries(field):
        for name, raw in planes:
            for f, entry in raw:
                if f == field:
                    kv = dict(_fields(entry))
                    yield name, kv.get(_MAP_KEY, 0), kv.get(_MAP_VALUE, b"")

    stat_names = {}
    for plane, key, value in map_entries(_XPLANE_STAT_MD):
        md = dict(_fields(value))
        stat_names[plane, md.get(_STAT_MD_ID, key)] = _text(
            md.get(_STAT_MD_NAME, b""))
    programs, ops = {}, []
    for plane, key, value in map_entries(_XPLANE_EVENT_MD):
        md = list(_fields(value))
        stats = {}
        for f, v in md:
            if f == _EVENT_MD_STATS:
                st = dict(_fields(v))
                stats[stat_names.get((plane, st.get(_STAT_ID)))] = st
        if plane == "/host:metadata" and "Hlo Proto" in stats:
            programs[key] = _program_scopes(
                stats["Hlo Proto"].get(_STAT_BYTES, b""))
        elif plane.startswith("/device:") and "program_id" in stats:
            st = stats["program_id"]
            pid = st.get(_STAT_I64, st.get(_STAT_U64, 0))
            text = next((_text(v) for f, v in md if f == _EVENT_MD_NAME), "")
            short = next((_text(v) for f, v in md
                          if f == _EVENT_MD_DISPLAY), "")
            ops.append((plane, text, pid, short))
    table = {}
    for plane, text, pid, short in ops:
        scope = programs.get(pid, {}).get(short)
        if scope:
            table.setdefault(plane, {})[text] = scope
    return table


# ---------------------------------------------------------------------------
# reduction


def find_trace(profile_dir: str) -> str:
    """The newest `*.xplane.pb` under `profile_dir`."""
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {profile_dir}")
    return max(files, key=os.path.getmtime)


def reduce_file(path: str, window: str | None = None,
                chips: int | None = None) -> dict:
    """The trace file at `path`, reduced as the module docstring says;
    `chips` keeps only TPU planes 0..chips-1."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    table = op_scope_table(path)
    devices, scoped, spans, host = {}, {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            if chips is not None and idx >= chips:
                continue
            scope_of = table.get(plane.name, {})
            ops, scoped[idx] = [], []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    if _opcode(e.name) in _CONTAINERS:
                        continue
                    ops.append([e.start_ns, e.duration_ns, e.name])
                    scope = scope_of.get(e.name)
                    if scope:
                        scoped[idx].append([e.start_ns, e.duration_ns,
                                            scope])
            devices[idx] = ops
        elif plane.name.startswith("/host:"):
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("sagips."):
                        spans.append([e.start_ns, e.duration_ns, e.name,
                                      thread, dict(e.stats)])
                    elif window is not None and e.name == window:
                        host.append((e.start_ns, e.duration_ns))
    n = max(devices, default=-1) + 1 if chips is None else chips
    every = [o for ops in devices.values() for o in ops] or spans
    if host:
        t0, t1 = min(host)[0], min(host)[0] + min(host)[1]
    elif every:
        t0 = min(o[0] for o in every)
        t1 = max(o[0] + o[1] for o in every)
    else:
        t0 = t1 = 0
    return {"t0": t0, "t1": t1,
            "devices": [devices.get(i, []) for i in range(n)],
            "scopes": [[o for o in scoped.get(i, [])
                        if o[0] < t1 and o[0] + o[1] > t0]
                       for i in range(n)],
            "spans": sorted(spans, key=lambda s: (s[0], s[3]))}


def _union(intervals, t0, t1):
    """Merged [start, end) intervals clipped to [t0, t1)."""
    out = []
    for s, e in sorted((max(s, t0), min(s + d, t1)) for s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals, t0, t1) -> int:
    return sum(e - s for s, e in _union(intervals, t0, t1))


def _per_chip_seconds(data, lists) -> float:
    n = len(lists)
    return sum(_length([(o[0], o[1]) for o in ops], data["t0"], data["t1"])
               for ops in lists) / n / 1e9 if n else 0.0


def scope_seconds(data, scope: str):
    """Device seconds of `scope`'s operations inside the window, averaged
    over chips; None where the trace has no operation of this scope."""
    per_chip = data.get("scopes") or []
    if not any(o[2] == scope for ops in per_chip for o in ops):
        return None
    return _per_chip_seconds(
        data, [[o for o in ops if o[2] == scope] for ops in per_chip])


def batches(data) -> list:
    """Each `sagips.solve.step` in the window that served a batch, as
    (step, {child name: child span}): the children are the spans of its
    thread that start inside it; a step without `.assemble` drained
    nothing."""
    by_thread = {}
    for s in data.get("spans", ()):
        by_thread.setdefault(s[3], []).append(s)
    out = []
    for spans in by_thread.values():
        spans.sort(key=lambda s: (s[0], -s[1]))
        for i, step in enumerate(spans):
            if step[2] != "sagips.solve.step" or not (
                    data["t0"] <= step[0] < data["t1"]):
                continue
            end = step[0] + step[1]
            kids = {}
            for s in spans[i + 1:]:
                if s[0] > end or s[2] == "sagips.solve.step":
                    break
                kids.setdefault(s[2], s)
            if "sagips.solve.assemble" in kids:
                out.append((step, kids))
    return out


def summary(data, top: int = 5) -> dict:
    """Seconds, averaged over chips, unless a key says ms:

    window_s, busy_s     the window, and the union of the device's ops in it
    scopes_s             per scope that the trace holds
    unscoped_s           busy time no scope covers
    unscoped_ops         the `top` op names that take most of it
    solve                None without served batches; else `batches`,
                         `step_ms` and each child's mean per batch
                         (`children_ms`), the share of the steps' time
                         their children cover, and `queue_wait_ms`, the
                         mean wait per request (admission to drain)
    """
    t0, t1 = data["t0"], data["t1"]
    devices, per_chip = data["devices"], data.get("scopes") or []
    scopes = {s: v for s in SCOPES
              if (v := scope_seconds(data, s)) is not None}
    busy = _per_chip_seconds(data, devices)
    covered = _per_chip_seconds(data, per_chip) if per_chip else 0.0
    left = {}
    for ops, mine in zip(devices, per_chip or [[]] * len(devices)):
        seen = {(o[0], o[1]) for o in mine}
        for o in ops:
            if (o[0], o[1]) not in seen:
                d = min(o[0] + o[1], t1) - max(o[0], t0)
                if d > 0:
                    left[o[2]] = left.get(o[2], 0) + d
    n = max(len(devices), 1)
    unscoped_ops = sorted(([name.split(" = ", 1)[0], ns / n / 1e9]
                           for name, ns in left.items()),
                          key=lambda x: -x[1])[:top]
    served = batches(data)
    solve = None
    if served:
        k = len(served)
        step_ns = sum(step[1] for step, _ in served)
        kids_ns = {c: sum(kids[c][1] for _, kids in served if c in kids)
                   for c in STEP_CHILDREN}
        args = [kids["sagips.solve.assemble"][4] for _, kids in served]
        n_req = sum(a["n"] for a in args)
        solve = {"batches": k,
                 "step_ms": step_ns / k / 1e6,
                 "children_ms": {c: v / k / 1e6 for c, v in kids_ns.items()},
                 "children_share": sum(kids_ns.values()) / step_ns,
                 "queue_wait_ms": (sum(a["wait_sum_us"] for a in args)
                                   / n_req / 1e3) if n_req else None}
    return {"window_s": (t1 - t0) / 1e9, "busy_s": busy, "scopes_s": scopes,
            "unscoped_s": busy - covered, "unscoped_ops": unscoped_ops,
            "solve": solve}
