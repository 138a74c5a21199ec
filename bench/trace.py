"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps two
lists: the device's operations (the ``XLA Ops`` line of each TPU plane),
each with its group (``op_group``), and the host's spans (every event on
the host plane: the benchmark's own ``TraceAnnotation``s and what the
runtime records).  ``reduce`` clips both to the window the benchmark
annotated and computes, from the leaf operations (a ``while`` that encloses
its body's operations is not itself counted):

- busy time: the union of the operations' intervals, averaged over chips;
- time by group: HLO opcode and fusion kind, never a numbered fusion name,
  so that a renumbering between compiles does not split a group;
- idle gaps: the stretches of the window with no operation, each named by
  the innermost host span around its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

#: the device plane and line that hold one event per operation run
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the group of scatter / segment-reduce operations (see ``op_group``)
SCATTER = "scatter"


@dataclasses.dataclass
class Events:
    """Device operations as ``(chip, group, start_ns, dur_ns)`` and host
    spans as ``(name, start_ns, dur_ns)``."""

    device_ops: list
    host_spans: list

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls([tuple(e) for e in d["device_ops"]],
                   [tuple(e) for e in d["host_spans"]])


def load(trace_dir: str) -> Events:
    """The events of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    ops, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            chip = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((chip, op_group(e.name), int(e.start_ns),
                                int(e.duration_ns)) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events)
    return Events(ops, spans)


_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_KIND = re.compile(r"kind=(k\w+)")


def _elements(dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def op_group(text: str) -> str:
    """Group of one operation, from its HLO text as the trace names it
    (``%fusion.30 = f32[38631424]{...} fusion(s32[89227264]{...} %a, ...),
    kind=kCustom, calls=...``): its opcode, with a fusion's kind
    (``fusion:kLoop``), so that renumbered fusions stay in one group.

    The trace does not hold the fused computations, so a scatter that XLA
    fused is known by its signature: an indexed reduction, a fusion whose
    output has fewer elements than an s32 operand that comes with a
    floating-point operand of as many elements (many indexed values summed
    into fewer slots).  That and the ``scatter`` opcode group as
    ``scatter``.  A gather's output has as many elements as its indices,
    so it stays ``fusion:kCustom``."""
    head, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    if not m:
        return re.sub(r"(\.\d+)+$", "", head.lstrip("%"))
    code = m.group(1)
    if code == "scatter":
        return SCATTER
    if code != "fusion":
        return code
    kind = _KIND.search(rest)
    group = "fusion:" + (kind.group(1) if kind else "unknown")
    out = _ARRAY.match(rest)
    operands = re.split(r"\), [a-z_]+=", rest[m.end() - 1:], maxsplit=1)[0]
    arrays = [(t, _elements(d)) for t, d in _ARRAY.findall(operands)]
    if out and not rest.startswith("("):
        n_out = _elements(out.group(2))
        floats = {n for t, n in arrays if t.startswith(("f", "bf"))}
        if any(t == "s32" and n > n_out and n in floats for t, n in arrays):
            return SCATTER
    return group


def leaves(ops: list) -> list:
    """The operations that enclose no other operation of their chip."""
    ops = sorted(ops, key=lambda o: (o[0], o[2], -o[3]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[0] != o[0] or nxt[2] >= o[2] + o[3]]


def union(intervals) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over chips
    by_group: dict  # group -> device seconds, summed over chips
    gaps: list  # [(host activity, seconds)], longest first

    def share(self, group: str) -> float | None:
        """Share of the device's busy time in operations of ``group``."""
        total = sum(self.by_group.values())
        return None if total <= 0 else self.by_group.get(group, 0.0) / total

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.by_group.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.gaps[:top]]}


def span(ev: Events, name: str) -> tuple:
    """Start and end, in ns, of the first host span named ``name``."""
    spans = [s for s in ev.host_spans if s[0] == name]
    if not spans:
        raise RuntimeError(f"no host span {name!r} in the trace")
    return spans[0][1], spans[0][1] + spans[0][2]


def reduce(ev: Events, window: str) -> Summary:
    """Clip ``ev`` to the first host span named ``window`` and reduce it."""
    lo, hi = span(ev, window)
    inner = [s for s in ev.host_spans
             if s[0] != window and s[1] < hi and s[1] + s[2] > lo]
    chips = sorted({o[0] for o in ev.device_ops}) or [0]
    by_group, busy, gaps = {}, 0, []
    ops = leaves(ev.device_ops)
    for chip in chips:
        ivs = []
        for c, group, s, d in ops:
            s, e = max(s, lo), min(s + d, hi)
            if c == chip and e > s:
                ivs.append((s, e))
                by_group[group] = by_group.get(group, 0) + (e - s)
        merged = union(ivs)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((activity(inner, (s + e) // 2), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / len(chips) / 1e9,
                   by_group={k: v / 1e9 for k, v in by_group.items()},
                   gaps=gaps)


def activity(spans, t: int) -> str:
    """The innermost (latest-starting) host span around ``t``."""
    around = [s for s in spans if s[1] <= t < s[1] + s[2]]
    return max(around, key=lambda s: (s[1], -s[2]))[0] if around else "idle"
