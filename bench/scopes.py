"""Device time by the program's own names: the ``jax.named_scope`` path of
every device operation in a profiler trace, and the host seconds of the
program's ``repro.obs`` spans.

Where the scope path is: XLA keeps a ``named_scope`` path in each
operation's ``op_name`` metadata (``jit(_pagerank_jit)/while/body/
pagerank.step/jit(_tocab_pull_jit)/tocab.gather/jit(_take)/select_n``),
and the TPU profiler writes it, with a ``:`` and the operation's type after
it, as the ``tf_op`` stat of the operation's event metadata on the device
plane's ``XLA Ops`` line (read by hand on a TPU v5e trace).
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
``load`` reads the few fields of ``tsl/profiler/protobuf/xplane.proto`` it
needs from the ``.xplane.pb`` wire format itself.

A scope's device seconds (``seconds``) are those of the leaf operations
(``bench.trace.leaves``) clipped to a window, where an operation counts for
a scope if that name is a component of its path: nested scopes count for
each enclosing name (``pagerank.step`` holds ``tocab.gather``).
"""
from __future__ import annotations

import glob
import os

from bench.trace import DEVICE_PLANE, OPS_LINE, leaves

#: the event-metadata stat that holds an operation's scope path
SCOPE_STAT = "tf_op"


def _fields(buf: bytes):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, bytes for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        shift = value = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            if b < 0x80:
                return value
            shift += 7

    while i < n:
        key = varint()
        wire = key & 7
        if wire == 0:
            yield key >> 3, varint()
        elif wire == 2:
            size = varint()
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _plane_ops(plane: bytes, chip: int) -> list:
    """``(chip, scope path, start_ns, dur_ns)`` of the ``XLA Ops`` line of
    one serialized ``XPlane``."""
    stat_names, metadata, lines = {}, {}, []
    for field, value in _fields(plane):
        if field == 3:  # XLine
            lines.append(value)
        elif field in (4, 5):  # map<int64, XEventMetadata | XStatMetadata>
            entry = dict(_fields(value))
            inner = dict(_fields(entry.get(2, b"")))
            if field == 5:
                stat_names[inner.get(1, 0)] = inner.get(2, b"").decode()
            else:
                metadata[inner.get(1, 0)] = [
                    dict(_fields(s)) for f, s in _fields(entry.get(2, b""))
                    if f == 5]
    scope_ids = {k for k, v in stat_names.items() if v == SCOPE_STAT}

    def scope(metadata_id: int) -> str:
        for stat in metadata.get(metadata_id, ()):
            if stat.get(1) in scope_ids:
                if 5 in stat:  # str_value
                    return stat[5].decode()
                if 7 in stat:  # ref_value: the name of a stat metadata
                    return stat_names.get(stat[7], "")
        return ""

    ops = []
    for line in lines:
        fields = list(_fields(line))
        if dict(fields).get(2, b"").decode() != OPS_LINE:
            continue
        t0_ns = dict(fields).get(3, 0)
        for f, event in fields:
            if f == 4:
                e = dict(_fields(event))
                ops.append((chip, scope(e.get(1, 0)),
                            t0_ns + e.get(2, 0) // 1000, e.get(3, 0) // 1000))
    return ops


def load(trace_dir: str) -> list:
    """Device operations of the one ``.xplane.pb`` under ``trace_dir`` as
    ``(chip, scope path, start_ns, dur_ns)``, on the clock of
    ``bench.trace.load``'s events."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    with open(paths[0], "rb") as f:
        space = f.read()
    ops = []
    for field, plane in _fields(space):
        if field != 1:  # XSpace.planes
            continue
        name = dict(_fields(plane)).get(2, b"").decode()
        if DEVICE_PLANE.match(name):
            ops.extend(_plane_ops(plane, int(name.rsplit(":", 1)[1])))
    return ops


def components(path: str) -> set:
    """The names in a scope path: ``/`` separates nesting, ``;`` the paths
    of operations XLA fused into one, ``:`` the operation's type."""
    return set(path.replace(";", "/").replace(":", "/").split("/"))


def seconds(ops: list, window: tuple, names) -> dict:
    """Device seconds of each scope in ``names`` within ``window`` (start
    and end in ns), summed over chips."""
    lo, hi = window
    out = dict.fromkeys(names, 0.0)
    for _, path, s, d in leaves(ops):
        clipped = min(s + d, hi) - max(s, lo)
        if clipped > 0:
            for name in components(path) & out.keys():
                out[name] += clipped / 1e9
    return out


def by_scope(ops: list, window: tuple) -> dict:
    """Device seconds within ``window`` of every scope name in ``ops``'
    paths, summed over chips: what a per-layer reader looks a scope up
    in."""
    names = set().union(*(components(o[1]) for o in ops)) - {""}
    return seconds(ops, window, names)


def per_step(run, name: str) -> float | None:
    """Device seconds of scope ``name`` in a run's traced window over the
    window's loop steps; None untraced, or where no operation carries the
    scope."""
    if run.scopes is None or name not in run.scopes or not sum(run.steps):
        return None
    return run.scopes[name] / sum(run.steps)


def host_seconds(*names) -> float | None:
    """Host seconds of the program's finished ``repro.obs`` spans with one
    of ``names``, summed; None where the program recorded none."""
    from repro.obs.trace import events

    durs = [e["dur_s"] for e in events() if e["name"] in names]
    return sum(durs) if durs else None
