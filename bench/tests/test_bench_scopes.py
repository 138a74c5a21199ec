"""Device time by program scope and host time by program span: the
``.xplane.pb`` reader on a hand-encoded trace, the reduction on a trace
recorded on a TPU v5e and on synthetic cases, the readers of the
set-up spans, and the readers of the slab phases' scopes."""
import json
import os
import types

import pytest

from bench import run as bench_run
from bench import scopes, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
PHASES = ("tocab.gather", "tocab.partials", "tocab.reduce")


# --------------------- xplane wire format, by hand --------------------- #
def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from ``(number, int | str | bytes)`` pairs."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name: str, line: str) -> bytes:
    """An XPlane with one line of three events: a scope path held as a
    string, one held by reference to a stat metadata, and none."""
    stat_md = [(5, _msg((1, k), (2, _msg((1, k), (2, v)))))
               for k, v in ((1, "tf_op"), (2, "flops"),
                            (3, "jit(f)/a.b/c:"))]
    event_md = [
        (4, _msg((1, 10), (2, _msg((1, 10), (2, "%fusion.1"),
                                   (5, _msg((1, 2), (4, 7))),
                                   (5, _msg((1, 1), (5, "jit(f)/x.y/z:"))))))),
        (4, _msg((1, 11), (2, _msg((1, 11), (2, "%scatter.2"),
                                   (5, _msg((1, 1), (7, 3))))))),
        (4, _msg((1, 12), (2, _msg((1, 12), (2, "%copy.3"))))),
    ]
    events = [(4, _msg((1, 10), (2, 5_000), (3, 2_000_000))),
              (4, _msg((1, 11), (2, 3_000_000), (3, 1_000_000))),
              (4, _msg((1, 12), (2, 4_000_000), (3, 500_000)))]
    return _msg((1, 7), (2, name), (3, _msg((2, line), (3, 1_000), *events)),
                *event_md, *stat_md)


def test_load_reads_scope_paths_from_the_wire(tmp_path):
    space = _msg((1, _plane("/device:TPU:0", "XLA Ops")),
                 (1, _plane("/device:TPU:0", "Async XLA Ops")),
                 (1, _plane("/host:CPU", "XLA Ops")),
                 (4, "hostname"))
    (tmp_path / "t.xplane.pb").write_bytes(space)
    assert scopes.load(str(tmp_path)) == [
        (0, "jit(f)/x.y/z:", 1_005, 2_000),
        (0, "jit(f)/a.b/c:", 4_000, 1_000),
        (0, "", 5_000, 500),
    ]


# ----------------------------- reduction ----------------------------- #
def test_components_split_nesting_fusion_and_type():
    assert scopes.components("jit(f)/p.step/t.gather/take:") >= {
        "p.step", "t.gather", "take"}
    assert {"t.partials", "t.reduce"} <= scopes.components(
        "jit(f)/t.partials/add;t.reduce/reshape:")
    assert "t.gather" not in scopes.components("jit(f)/t.gatherer/x:")


def test_nested_scopes_count_for_each_enclosing_name():
    ops = [
        (0, "", 0, 100),  # a loop that encloses the others: not a leaf
        (0, "jit(f)/while/body/step/gather/take:", 0, 30),
        (0, "jit(f)/while/body/step/partials/scatter-add:", 30, 40),
        (0, "jit(f)/while/body/step/reduce/scatter-add:", 70, 20),
        (0, "jit(f)/while/body/step/sub:", 90, 10),
        (0, "jit(f)/copy:", 100, 50),  # after the window's end
    ]
    got = scopes.seconds(ops, (0, 100),
                         ["step", "gather", "partials", "reduce", "while"])
    assert got == pytest.approx({"step": 100e-9, "gather": 30e-9,
                                 "partials": 40e-9, "reduce": 20e-9,
                                 "while": 100e-9})


def test_seconds_clip_to_the_window():
    ops = [(0, "jit(f)/a/x:", 0, 100), (0, "jit(f)/a/y:", 150, 100)]
    assert scopes.seconds(ops, (50, 200), ["a", "b"]) == pytest.approx(
        {"a": 100e-9, "b": 0.0})


@pytest.fixture(scope="module")
def recorded():
    """A kron scale-16 PageRank to tol 1e-4 and a jitted push, then an
    eager edge reduce (its operations, one jit each, carry no scope),
    under one ``bench.solve`` span, recorded on a TPU v5e."""
    with open(os.path.join(DATA, "pagerank_scoped_trace.json")) as f:
        ev = trace.Events.from_json(json.load(f))
    [solve] = [h for h in ev.host_spans if h[0] == "bench.solve"]
    return ev, (solve[1], solve[1] + solve[2])


def test_recorded_trace_names_every_slab_phase(recorded):
    ev, window = recorded
    names = ("pagerank.step",) + PHASES
    got = scopes.seconds(ev.device_ops, window, names)
    assert all(got[n] > 0 for n in names), got
    assert got["pagerank.step"] <= trace.reduce(ev, "bench.solve").busy_s
    # the push path carries the same three names
    push = {n for o in ev.device_ops if o[1].startswith("jit(_tocab_push_jit)")
            for n in scopes.components(o[1])}
    assert set(PHASES) <= push


def test_recorded_step_is_its_phases(recorded):
    """PageRank's pull phases lie inside its step and take nearly all of
    it; the rest is the contributions, the apply and the L1 delta."""
    ev, window = recorded
    pagerank = [o for o in ev.device_ops if "pagerank.step" in o[1]]
    step = scopes.seconds(pagerank, window, ["pagerank.step"])["pagerank.step"]
    phases = sum(scopes.seconds(pagerank, window, PHASES).values())
    assert 0.95 * step <= phases <= step


def test_recorded_host_plane_holds_the_program_span(recorded):
    ev, window = recorded
    [pr] = [h for h in ev.host_spans if h[0] == "pagerank"]
    assert window[0] <= pr[1] and pr[1] + pr[2] <= window[1]


# ------------------------- set-up span readers ------------------------- #
READS = {"layout_sort_s": ("build_blocked.sort",),
         "layout_fill_s": ("build_blocked.fill",),
         "layout_place_s": ("build_blocked.place", "device_graph.place")}


def _reader(name):
    return bench_run.load_module(
        os.path.join(bench_run.BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("name", sorted(READS))
def test_setup_reader_is_none_without_spans(name, monkeypatch):
    from repro.obs import trace as obs_trace

    monkeypatch.setattr(obs_trace, "events", lambda: [
        {"name": "build_blocked", "dur_s": 9.0}, {"name": "pagerank",
                                                  "dur_s": 1.0}])
    assert _reader(name).read(None) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_setup_reader_sums_its_spans(name, monkeypatch):
    from repro.obs import trace as obs_trace

    events = [{"name": "build_blocked", "dur_s": 100.0}]
    for i, span in enumerate(READS[name] * 2):
        events.append({"name": span, "dur_s": 0.5 * (i + 1)})
    monkeypatch.setattr(obs_trace, "events", lambda: events)
    n = len(READS[name]) * 2
    assert _reader(name).read(None) == pytest.approx(
        0.5 * n * (n + 1) / 2)


# --------------------- slab-phase readers, by scope --------------------- #
SLAB = {"slab_gather_s": "tocab.gather", "slab_partials_s": "tocab.partials",
        "slab_reduce_s": "tocab.reduce"}
STEP = "jit(_pagerank_jit)/while/body/pagerank.step/jit(_tocab_pull_jit)"
SYNTHETIC = [
    (0, "", 0, 1000),  # the loop that encloses the rest: not a leaf
    (0, STEP + "/tocab.gather/jit(_take)/select_n:", 0, 300),
    (0, STEP + "/tocab.partials/scatter-add:", 300, 200),
    (0, STEP + "/tocab.partials/add;" + STEP + "/tocab.reduce/reshape:",
     500, 100),  # one fusion of two phases counts for both
    (0, STEP + "/tocab.reduce/scatter-add:", 600, 350),
    (0, STEP + "/sub:", 950, 50),
    (0, STEP + "/tocab.gather/take:", 1000, 500),  # after the window
]


def test_by_scope_keeps_every_name_in_the_window():
    got = scopes.by_scope(SYNTHETIC, (0, 1000))
    assert "" not in got
    assert got["pagerank.step"] == pytest.approx(1000e-9)
    assert got["tocab.gather"] == pytest.approx(300e-9)
    assert got["scatter-add"] == pytest.approx(550e-9)
    assert scopes.by_scope([], (0, 1000)) == {}


@pytest.mark.parametrize("name", sorted(SLAB))
def test_slab_reader_reads_its_scope_per_step(name):
    run = types.SimpleNamespace(
        scopes=scopes.by_scope(SYNTHETIC, (0, 1000)), steps=[3, 2])
    want = {"tocab.gather": 300e-9, "tocab.partials": 300e-9,
            "tocab.reduce": 450e-9}[SLAB[name]] / 5
    assert _reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SLAB))
def test_slab_reader_is_none_without_its_scope(name):
    reader = _reader(name)
    assert reader.read(types.SimpleNamespace(scopes=None, steps=[3])) is None
    other = scopes.by_scope([(0, "jit(f)/bfs.level/x:", 0, 10)], (0, 10))
    assert reader.read(types.SimpleNamespace(scopes=other, steps=[3])) is None
