"""The arithmetic from trace events to numbers, pinned on a synthetic list
of events: busy and idle share, self times of nested operations, the
attribution of idle gaps to what the host was doing, per-module totals."""


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
        self.trace_summary, self.counts = summary, counts or {}
        self.notes = notes or {}
        self.shapes = {"vertices": 1 << 20, "edges": 1 << 24}


def _readers():
    import run as bench

    return bench.Catalog([REPO]).plugins("readers", "READERS")


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
