"""The share of a program's device time that its operations under one
`named_scope` took, for any per-layer metric that asks for one: self time
(a loop's body is counted by its own operations, not once more by the
loop), over the operations that begin inside the named program in the
traced slice.

An operation's scope is not on its event but in the plane's table of
event metadata: the entry of an operation has a stat `tf_op` whose text
is the path the operation was traced under
(`jit(decode)/while/body/mtp/full/...`; a fusion bears its root's), and a
stat `program_id`, the number in the program's own name
(`jit_decode(1778...)`). `jax.profiler.ProfileData` does not hand that
table out, so `scopes` walks the file's own bytes for it (the top level
of the first device plane only: the lines of events are stepped over).
The events, with their times, come from `ProfileData` as
`device_modules.py` reads them, and an event's name is its entry's.

This is `layer_metrics/linear_attention_device_pct.lm.py`'s reading (PR
38) with the scope and the programs as arguments; that file keeps its own
copy until a `benchmark` PR may edit it, and a later scoped metric
imports this one."""

import bisect
import re

import device_modules
import xplane

_LOADED: dict = {}


def _varint(data, at: int):
    value = shift = 0
    while True:
        byte = data[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, at


def _fields(data):
    """(number, value) of each field of one protobuf message: an int, or
    a view of a length-delimited field's bytes."""
    at = 0
    while at < len(data):
        key, at = _varint(data, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(data, at)
        elif wire == 2:
            size, at = _varint(data, at)
            value, at = data[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = data[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire}")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def scopes(path: str, names: tuple) -> dict:
    """{an operation's name: its `tf_op` text} over the operations of the
    programs called `names` in the first device plane of the trace at `path`
    (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_
    metadata = 5, both maps of key = 1, value = 2; XEventMetadata.name =
    2, .stats = 5; XStat.metadata_id = 1, .uint64_value = 3, .str_value =
    5, .ref_value = 7; XStatMetadata.name = 2)."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, entries, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = _text(value)
            elif field == 4:
                entries.append(dict(_fields(value)).get(2))
            elif field == 5:
                pair = dict(_fields(value))
                stat_names[pair.get(1)] = _text(dict(_fields(pair.get(2, b""))).get(2, b""))
        if not xplane.DEVICE_PLANE.match(name):
            continue
        found, programs = [], set()
        for entry in entries:
            if entry is None:
                continue
            title, scope, program = "", "", None
            for field, value in _fields(entry):
                if field == 2:
                    title = _text(value)
                elif field == 5:
                    stat = dict(_fields(value))
                    key = stat_names.get(stat.get(1))
                    if key == "tf_op":
                        scope = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
                    elif key == "program_id":
                        program = stat.get(3, stat.get(4))
            module = re.fullmatch(r"(\w+)\((\d+)\)", title)
            if module and module.group(1) in names:
                programs.add(int(module.group(2)))
            found.append((title, scope, program))
        return {title: scope for title, scope, program in found if program in programs}
    return {}


def _within(programs: list):
    """-> inside(start_ns): whether it lies in one of `programs`
    [(start_ns, end_ns)], which do not overlap."""
    starts, ends = zip(*sorted(programs)) if programs else ((), ())

    def inside(start: int) -> bool:
        at = bisect.bisect_right(starts, start) - 1
        return at >= 0 and start < ends[at]

    return inside


def operations(path: str, programs: list, names: tuple) -> list:
    """[(start_ns, end_ns, scope text), ...] of the first device plane's
    "XLA Ops" line, of the operations that begin inside one of `programs`
    [(start_ns, end_ns)], the runs of the programs called `names`."""
    from jax.profiler import ProfileData

    try:
        table = scopes(path, names)
    except (ValueError, IndexError):  # not the bytes of an XSpace: nothing to read
        table = {}
    inside = _within(programs)
    for plane in ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if (line.name or xplane.OPS_LINE) != xplane.OPS_LINE:
                continue
            found = []
            for event in line.events:
                start = int(event.start_ns)
                if inside(start):
                    found.append(
                        (start, start + int(event.duration_ns), table.get(event.name, "")))
            return found
    return []


def self_time_pct(operations: list, programs: list, scope):
    """`operations` of `operations()`, `programs` [(start_ns, end_ns)],
    `scope` a compiled pattern over an operation's path: 100 x self time
    under the scope / self time, over the operations that begin inside a
    program; None where none of them names a scope."""
    within = _within(programs)
    inside = sorted((op for op in operations if within(op[0])), key=lambda op: (op[0], -op[1]))
    if not any("/" in text for _, _, text in inside):
        return None
    total = scoped = 0
    stack: list = []  # [end, self_ns, under the scope]

    def close(entry) -> None:
        nonlocal total, scoped
        total += max(0, entry[1])
        scoped += max(0, entry[1]) if entry[2] else 0

    for start, end, text in inside:
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][1] -= min(end, stack[-1][0]) - start
        stack.append([end, end - start, bool(scope.search(text))])
    while stack:
        close(stack.pop())
    return 100.0 * scoped / total if total else None


def under(scope: str):
    """The pattern of a path that passes through the scope `scope`."""
    return re.compile(rf"/{re.escape(scope)}(/|$)")


def share_pct(material, names: tuple, scope: str):
    """The reading of a metric: of the device time of the programs called
    `names` in the traced slice, the per cent under `scope`. None where
    there is no trace, no such program in it, or no operation of those
    programs says anything of a scope."""
    programs = [
        (start, end) for name, start, end in device_modules.modules(material) if name in names]
    if not programs:
        return None
    path = xplane.find_trace(device_modules.profile_dir())
    if (path, names) not in _LOADED:
        _LOADED[path, names] = operations(path, programs, names)
    return self_time_pct(_LOADED[path, names], programs, under(scope))
