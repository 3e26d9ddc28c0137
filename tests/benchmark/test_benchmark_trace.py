"""The arithmetic from trace events to numbers, pinned on a synthetic list
of events: busy and idle share, self times of nested operations, the
attribution of idle gaps to what the host was doing, per-module totals,
device time by named scope; and the reader of a profile's name stacks,
pinned on a synthetic protobuf."""


import pytest

from rehearsal import REPO  # puts benchmark/ on sys.path

import device  # noqa: E402
import trace_reduce as tr  # noqa: E402

MS = 1_000_000  # the trace's clock is in nanoseconds


def events():
    """A 100 ms window on one device: a fused program of 40 ms (a `while`
    holding two fusions, with a 2 ms hole), 20 ms idle while the host
    fetches, a 10 ms superstep, 30 ms idle in plain Python."""
    return {
        "window": (0, 100 * MS),
        "devices": {"/device:TPU:0": {
            "modules": [("jit_run_span", 0, 40 * MS),
                        ("jit_superstep", 60 * MS, 70 * MS)],
            "ops": [("while.1", 0, 40 * MS),
                    ("fusion.1 f32[8]", 0, 25 * MS),
                    ("fusion.2 f32[8]", 27 * MS, 40 * MS),
                    ("fusion.3 f32[4]", 60 * MS, 70 * MS)],
        }},
        "spans": [("bench:submit", 0, 58 * MS),
                  ("bench:fetch", 41 * MS, 58 * MS),
                  ("bench:submit", 59 * MS, 100 * MS)],
        "activities": [("np.asarray(jax.Array)", 42 * MS, 58 * MS)],
    }


def test_union_gaps_and_overlap():
    cover = tr.union([(5, 7), (0, 3), (2, 4), (7, 9), (20, 20)])
    assert cover == [(0, 4), (5, 9)]
    assert tr.covered(cover) == 8
    assert tr.gaps(cover, 0, 12) == [(4, 5), (9, 12)]
    assert tr.gaps(cover, 1, 8) == [(4, 5)]
    assert tr.gaps([], 0, 3) == [(0, 3)]
    assert tr.overlap(cover, 3, 6) == 2
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_self_times_do_not_count_a_loop_body_twice():
    got = tr.self_times(events()["devices"]["/device:TPU:0"]["ops"])
    assert got["while.1"] == 2 * MS          # only the hole is its own
    assert got["fusion.1 f32[8]"] == 25 * MS
    assert got["fusion.2 f32[8]"] == 13 * MS
    assert sum(got.values()) == 50 * MS      # = the union: nothing twice


def test_reduce_gives_the_known_busy_share_and_module_totals():
    out = tr.reduce(events())
    assert out["window_s"] == pytest.approx(0.100)
    # the while op covers its hole, so the device is busy for 40 + 10 ms
    assert out["busy_s"] == pytest.approx(0.050)
    assert out["devices"] == 1
    assert out["modules"]["jit_run_span"] == [1, pytest.approx(0.040)]
    assert out["modules"]["jit_superstep"] == [1, pytest.approx(0.010)]
    ops = dict(out["device_ops"])
    assert ops["jit_run_span/fusion.1 f32[8]"] == pytest.approx(0.025)
    assert ops["jit_superstep/fusion.3 f32[4]"] == pytest.approx(0.010)
    assert ops["jit_run_span/while.1"] == pytest.approx(0.002)


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    gaps = dict(tr.reduce(events())["idle_gaps"])
    # 40..60: mostly inside the fetch span and the traced np.asarray
    assert gaps["bench:submit/np.asarray(jax.Array)"] == pytest.approx(0.020)
    # 70..100: in a submit span, nothing traced: interpreter code
    assert gaps["bench:submit/python"] == pytest.approx(0.030)
    assert sum(gaps.values()) == pytest.approx(0.050)


def test_a_hole_inside_a_running_program_is_the_programs():
    ev = events()
    ev["devices"]["/device:TPU:0"]["ops"].remove(("while.1", 0, 40 * MS))
    out = tr.reduce(ev)
    assert out["busy_s"] == pytest.approx(0.048)
    assert dict(out["idle_gaps"])["device/in_program_bubble"] == (
        pytest.approx(0.002))


def test_window_clips_and_two_devices_average():
    ev = events()
    ev["window"] = (20 * MS, 100 * MS)
    ev["devices"]["/device:TPU:1"] = {"modules": [], "ops": []}
    out = tr.reduce(ev)
    assert out["window_s"] == pytest.approx(0.080)
    assert out["busy_s"] == pytest.approx((0.030 + 0.0) / 2)
    # a module that began before the window is not a whole execution
    assert "jit_run_span" not in out["modules"]
    assert tr.reduce({"devices": {}, "spans": [], "activities": []}) is None


def test_short_gaps_are_summed_not_attributed():
    got = tr.attribute_gaps(
        [(0, 5), (10, 2000)], [("bench:request", 0, 3000)], [], short=10)
    assert got == {"short_gaps/unattributed": 5,
                   "bench:request/python": 1990}
    assert tr.attribute_gaps([(0, 10)], [], []) == {"no_span/python": 10}


@pytest.mark.parametrize("text,want", [
    ("%fusion.168 = f32[1560576]{0:T(1024)S(1)} fusion(f32[262145]{0} %p), "
     "kind=kCustom", "fusion.168 f32[1560576]"),
    ("%while.35 = (f32[1048576]{0}, s32[]) while(%tuple)", "while.35 f32[1048576]"),
    ("%copy-start.50 = (f32[14]{0:T(128)S(1)}, u32[]) copy-start(%x)",
     "copy-start.50 f32[14]"),
    ("no equals sign here", "no equals sign here"),
])
def test_short_op_names(text, want):
    assert device.short_op(text) == want


class _Run:
    def __init__(self, summary, counts=None, notes=None):
        import run as bench

        self.trace_summary, self.counts = summary, counts or {}
        self.notes = notes or {}
        self.shapes = {"vertices": 1 << 20, "edges": 1 << 24}
        self.catalog = bench.Catalog([REPO])


def _readers():
    return _Run(None).catalog.plugins("readers", "READERS")


def test_trace_readers_divide_device_time_by_the_units_they_are_given():
    summary = tr.reduce(events())
    readers = _readers()
    per_superstep = readers["trace-ops"](
        _Run(summary, {"supersteps_traced": 20}),
        modules=["jit_run_span"], per={"count": "supersteps_traced"})
    assert per_superstep == pytest.approx(2.0)           # 40 ms / 20
    per_execution = readers["trace-ops"](
        _Run(summary), modules=["jit_superstep", "jit_run_span"],
        per={"executions_of": ["jit_superstep"]})
    assert per_execution == pytest.approx(50.0)
    assert readers["trace-idle"](_Run(summary)) == pytest.approx(50.0)
    # nothing to read: the metric is left out
    assert readers["trace-idle"](_Run(None)) is None
    assert readers["trace-ops"](
        _Run(summary), modules=["jit_step"],
        per={"executions_of": ["jit_step"]}) is None


def test_roofline_reader_counts_the_algorithms_bytes_against_the_peak():
    readers = _readers()
    run = _Run(tr.reduce(events()), {"supersteps_traced": 20},
               {"run_info": {"path": "fused"}})

    class _Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    run.devices = [_Dev()]
    args = dict(bytes_function="dense-superstep", paths=["fused"],
                modules=["jit_run_span"], per={"count": "supersteps_traced"})
    bytes_moved = 8 * (1 << 24) + 12 * (1 << 20)
    want = 100.0 * (1000.0 * bytes_moved / 819e9) / 2.0
    assert readers["roofline"](run, **args) == pytest.approx(want)
    run.notes = {"run_info": {"path": "frontier"}}
    assert readers["roofline"](run, **args) is None
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


# ------------------------------------------------- device time by named scope

def scoped_events():
    """`events()` with a name stack on each operation: the program's first
    fusion under `gather`, its second under `fold` inside `gather`, the
    loop itself with none (as the profile has it), the superstep's fusion
    under `fold` alone."""
    ev = events()
    body = "jit(run_span)/jit(main)/while/body/"
    ev["devices"]["/device:TPU:0"]["stacks"] = [
        ("", 0, 40 * MS),
        (body + "gather/jit(_take)/gather:", 0, 25 * MS),
        (body + "gather/fold/reduce_max:", 27 * MS, 40 * MS),
        ("jit(superstep)/pjit/fold/cond/branch_1_fun/add:", 60 * MS, 70 * MS),
    ]
    return ev


@pytest.mark.parametrize("stack,want", [
    ("jit(run_span)/while/body/jit(_take)/gather:", []),
    ("jit(run_span)/jit(main)/while/body/fold/reduce_max:", ["fold"]),
    ("jit(f)/vmap(jit(g))/outer/pjit/inner/cond/branch_0_fun/mul:",
     ["outer", "inner"]),
    ("jit(f)/a/b/a/add:XlaOp", ["a", "b"]),   # a scope inside itself: once
    ("jit(f)/gather/gather:", ["gather"]),    # the last is the primitive
    ("jit(multiply)/mul:", []),
    ("", []),
])
def test_scopes_of_a_name_stack_leave_out_frames_and_the_primitive(stack,
                                                                   want):
    assert tr.scopes_of(stack) == want


def test_scope_times_are_totals_of_self_times():
    """The key holds a total: the self time of every operation under the
    scope at any depth. `fold` inside `gather` counts under both; the loop,
    which has no name stack, keeps its own 2 ms out of every scope."""
    out = tr.reduce(scoped_events())
    assert out["scopes"] == {
        "gather": pytest.approx(0.025 + 0.013),
        "fold": pytest.approx(0.013 + 0.010),
    }
    # nothing else moved: the keys that were there read what they read
    plain = tr.reduce(events())
    assert plain["scopes"] == {}
    for key in ("window_s", "busy_s", "modules", "device_ops", "idle_gaps"):
        assert out[key] == plain[key], key


def test_scope_times_clip_to_the_window_and_sum_over_devices():
    ev = scoped_events()
    ev["window"] = (20 * MS, 100 * MS)
    ev["devices"]["/device:TPU:1"] = {
        "modules": [], "ops": [("fusion.9 f32[8]", 30 * MS, 34 * MS)],
        "stacks": [("jit(run_span)/while/body/fold/add:", 30 * MS, 34 * MS)],
    }
    out = tr.reduce(ev)
    assert out["scopes"] == {
        "gather": pytest.approx(0.005 + 0.013),        # 20..25 and 27..40
        "fold": pytest.approx(0.013 + 0.010 + 0.004),  # both devices
    }
    # a device with operations and no name stacks adds nothing
    del ev["devices"]["/device:TPU:1"]["stacks"]
    assert tr.reduce(ev)["scopes"]["fold"] == pytest.approx(0.023)


def test_trace_scope_reader_and_a_roofline_by_scope():
    readers = _readers()
    summary = tr.reduce(scoped_events())
    run = _Run(summary, {"supersteps_traced": 20})
    per = {"count": "supersteps_traced"}
    assert readers["trace-scope"](run, scopes=["fold"], per=per) == (
        pytest.approx(23.0 / 20))
    assert readers["trace-scope"](
        run, scopes=["fold", "nowhere"],
        per={"executions_of": ["jit_superstep"]}) == pytest.approx(23.0)
    # nothing to read: no summary, a summary from before the key, a scope
    # the trace does not hold, nothing to divide by
    assert readers["trace-scope"](_Run(None), scopes=["fold"], per=per) is None
    old = {k: v for k, v in summary.items() if k != "scopes"}
    assert readers["trace-scope"](
        _Run(old, {"supersteps_traced": 20}), scopes=["fold"], per=per) is None
    assert readers["trace-scope"](run, scopes=["nowhere"], per=per) is None
    assert readers["trace-scope"](
        _Run(summary), scopes=["fold"], per=per) is None

    class _Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    run.devices = [_Dev()]
    bytes_moved = 8 * (1 << 24) + 12 * (1 << 20)
    want = 100.0 * (1000.0 * bytes_moved / 819e9) / (38.0 / 20)
    assert readers["roofline"](
        run, bytes_function="dense-superstep", scopes=["gather"], per=per
    ) == pytest.approx(want)
    assert readers["roofline"](
        run, bytes_function="dense-superstep", scopes=["nowhere"], per=per
    ) is None


def test_a_bytes_function_comes_with_the_files_a_root_adds(tmp_path):
    """A later PR names its scope and its bytes function in files of its
    own: a `readers/*.py` under a root offers a `BYTES` table too."""
    import run as bench

    readers = tmp_path / "benchmark" / "readers"
    readers.mkdir(parents=True)
    (readers / "fold.py").write_text(
        "BYTES = {'fold': lambda shapes: 4 * shapes['edges']}\n")
    table = bench.Catalog([str(tmp_path), REPO]).plugins("readers", "BYTES")
    assert table["fold"]({"edges": 10}) == 40
    assert "dense-superstep" in table


# ------------------------------------- the profile's name stacks, from the file

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of (number, int or bytes) fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _plane(name, stat_names, metadata, lines):
    """An XPlane: stat_names {id: name}; metadata {id: (name, [XStat])};
    lines [(name, timestamp_ns, [(metadata id, offset_ps, duration_ps)])]."""
    fields = [(1, 7), (2, name.encode())]
    for line_name, at, evs in lines:
        fields.append((3, _msg(
            (1, 1), (2, line_name.encode()), (3, at),
            *[(4, _msg((1, m), (2, off), (3, dur),
                       (4, _msg((1, 99), (3, 5)))))  # an event's own stat
              for m, off, dur in evs])))
    for key, (md_name, stats) in metadata.items():
        fields.append((4, _msg((1, key), (2, _msg(
            (1, key), (2, md_name.encode()), (4, b"display"),
            *[(5, stat) for stat in stats])))))
    for key, stat_name in stat_names.items():
        fields.append((5, _msg((1, key), (2, _msg(
            (1, key), (2, stat_name.encode()))))))
    return _msg(*fields)


def test_name_stacks_are_read_from_the_metadata_of_each_device_operation(
        tmp_path):
    stat_names = {1: "flops", 2: device.STACK_STAT,
                  3: "jit(f)/while/body/shared/mul:"}
    fixed64 = _varint(2 << 3 | 1) + b"\0" * 8   # a double_value: stepped over
    metadata = {
        10: ("%fusion.1 = f32[8] fusion()", [
            _msg((1, 1), (4, 1234)) + fixed64,
            _msg((1, 2), (5, b"jit(f)/while/body/fold/add:"))]),
        11: ("%while.1 = () while()", [_msg((1, 1), (4, 0))]),
        12: ("%fusion.2 = f32[8] fusion()", [_msg((1, 2), (7, 3))]),
    }
    ops = [(11, 0, 9_000_000), (10, 1_000_000, 2_500_000), (12, 4_000_000, 500)]
    space = _msg(
        (1, _plane("/device:TPU:0", stat_names, metadata, [
            ("XLA Modules", 1000, [(11, 0, 9_000_000)]),
            ("XLA Ops", 1000, ops)])),
        (1, _plane("/host:CPU", stat_names, metadata,
                   [("XLA Ops", 0, ops)])),
        (4, b"hostname"),
    )
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert device.name_stacks(str(path)) == {"/device:TPU:0": [
        ("", 1000.0, 10000.0),
        ("jit(f)/while/body/fold/add:", 2000.0, 4500.0),
        ("jit(f)/while/body/shared/mul:", 5000.0, 5000.5),
    ]}
