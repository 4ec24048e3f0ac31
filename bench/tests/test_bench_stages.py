"""The stage-span reduction (bench/stages.py) on hand-made events and on a
small recorded chip trace of dgn.screen that carries the program's
``repro.*`` spans, and the harness's own reduction of the recorded
gin.screen trace, which holds none of them."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import stages  # noqa: E402

DATA = BENCH / "tests" / "data"


def _load(name):
    trace = json.loads((DATA / name).read_text())
    trace["devices"] = {int(k): v for k, v in trace["devices"].items()}
    trace["window"] = devtrace.window(trace)
    return trace


def _spans(flushes, offset=0):
    """``repro.*`` spans of one call holding ``flushes``: each flush a
    list of (stage, start, end)."""
    out = []
    for f in flushes:
        out.append(["repro.flush", f[0][1] + offset - 5, f[-1][2] + offset + 10])
        out += [["repro." + st, s + offset, e + offset] for st, s, e in f]
    return out


FLUSH_A = [("eigvec", 25, 60), ("pack", 60, 150), ("layout", 100, 140),
           ("dispatch", 150, 170), ("device_wait", 170, 250), ("d2h", 250, 260),
           ("unpack", 260, 290)]
FLUSH_B = [("pack", 320, 400), ("layout", 350, 380), ("dispatch", 400, 410),
           ("compile", 402, 408), ("device_wait", 410, 500), ("d2h", 500, 510),
           ("unpack", 510, 570)]


@pytest.fixture
def made():
    """Two flushes in one call, a generate, then a call with no flush."""
    programs = [["jit_dynamic_slice(2)", 110, 112], ["jit_squeeze(3)", 130, 131],
                ["jit_run(1)", 175, 240], ["jit_run(1)", 415, 490],
                ["jit_iota(4)", 620, 621]]
    program = ([["repro.run", 10, 590]] + _spans([FLUSH_A, FLUSH_B])
               + [["repro.run", 660, 990]])
    return {"window": (0, 1000),
            "host": [["bench.window", 0, 1000], ["bench.serve", 0, 600],
                     ["bench.generate", 600, 650], ["bench.serve", 650, 1000]],
            "program": sorted(program, key=lambda s: (s[1], -s[2])),
            "devices": {0: {"ops": [list(p) for p in programs],
                            "programs": programs}}}


def test_stage_times_are_clipped_sums_over_flushes(made):
    ms = stages.stage_ms(made)
    # flush spans are [20, 300] and [315, 580]; the runs 580 and 330 ns
    assert ms["flush"] == pytest.approx((280 + 265) / 2 / 1e6)
    assert ms["run"] == pytest.approx((580 + 330) / 2 / 1e6)
    assert ms["sched"] == pytest.approx((910 - 545) / 2 / 1e6)
    assert ms["eigvec"] == pytest.approx(35 / 2 / 1e6)
    assert ms["pack"] == pytest.approx((90 + 80) / 2 / 1e6)
    assert ms["layout"] == pytest.approx((40 + 30) / 2 / 1e6)
    assert ms["compile"] == pytest.approx(6 / 2 / 1e6)
    assert ms["unpack"] == pytest.approx((30 + 60) / 2 / 1e6)
    # a window cut through a span counts only its part inside
    cut = dict(made, window=(0, 200))
    assert stages.stage_ms(cut)["pack"] == pytest.approx(90 / 1e6)
    assert stages.stage_ms(cut)["device_wait"] == pytest.approx(30 / 1e6)


def test_eager_calls_are_other_programs_starting_inside_a_flush(made):
    # two round trips in the first flush's pack; the served program and
    # the call between flushes do not count
    assert stages.eager_calls_per_flush(made) == 1.0


def test_unattributed_idle_is_serving_idle_no_stage_covers(made):
    # idle inside bench.serve: [0,110] [112,130] [131,175] [240,415]
    # [490,600] [650,1000] = 807 ns; stage spans cover [25,290] and
    # [320,570]: 85 + 18 + 44 + 145 + 80 = 372 ns
    assert stages.unattributed_idle_share(made) == pytest.approx(1 - 372 / 807)
    assert stages.covered_ns([(0, 10), (20, 30)], [(5, 25), (8, 9)]) == 10


def test_idle_gaps_are_named_by_the_innermost_span_over_most_of_them(made):
    # e.g. [240, 415]: device_wait 15, d2h 10, unpack 30, flush 15, run 15,
    # pack 30 + 20 around layout 30, dispatch 4, compile 6 -> pack, 50 ns
    assert stages.idle_gaps(made) == [
        ["repro.run", 379e-9, 330 / 379], ["repro.pack", 175e-9, 50 / 175],
        ["repro.unpack", 130e-9, 60 / 130], ["repro.pack", 110e-9, 40 / 110],
        ["repro.dispatch", 44e-9, 20 / 44], ["repro.layout", 18e-9, 1.0]]
    assert stages.holder([], 0, 10) == ("host: no span", 1.0)


def test_reduce_needs_one_flush_span_per_logged_flush(made):
    out = stages.reduce(made, 2)
    assert out["flushes_traced"] == 2
    assert out["stage_sum_ms_per_flush"] == pytest.approx(
        (910 - 545 + 35 + 170 + 30 + 170 + 20 + 90) / 2 / 1e6)
    assert out["unpack_ms_per_flush"] == pytest.approx((20 + 90) / 2 / 1e6)
    assert out["stage_ms"]["unpack"] == pytest.approx(90 / 2 / 1e6)
    assert out["pack_ms_per_flush"] == out["stage_ms"]["pack"]
    assert stages.reduce(made, 3) == {"flushes_traced": 2, "flushes_logged": 3}


def test_trimmed_keeps_three_flushes_each_side_of_a_call_boundary(made):
    one = [[("pack", 10, 20), ("device_wait", 20, 30)]]
    first = sum((_spans(one, 100 * k) for k in range(4)), [])
    second = sum((_spans(one, 1000 + 100 * k) for k in range(4)), [])
    trace = {"window": (0, 2000),
             "host": [["bench.window", 0, 2000], ["bench.serve", 0, 900],
                      ["bench.serve", 1000, 1900]],
             "program": first + second,
             "devices": {0: {"ops": [["op", 1015, 1018]], "programs": []}}}
    part = stages.trimmed(trace, "made")
    part["window"] = devtrace.window(part)
    assert part["window"] == (40, 1305)  # end of flush 0, start of flush 7
    assert len(stages.flushes(part)) == 6
    assert part["devices"][0]["ops"] == [["op", 1015, 1018]]
    assert stages.trimmed(dict(trace, program=first), "made") is None


# --------------------------------------------------- the recorded traces


def test_a_trace_without_program_spans_reports_no_stage_numbers():
    """The recorded gin.screen trace predates the program's spans: every
    reading is left out, and the harness's own reduction of it is as it
    was (busy time and idle gaps, named by the harness's spans)."""
    trace = _load("trace_gin_screen.json")
    assert stages.reduce(trace, 6) == {"flushes_traced": 0, "flushes_logged": 6}
    assert stages.eager_calls_per_flush(trace) is None
    assert stages.unattributed_idle_share(trace) is None
    lo, hi = trace["window"]
    ops = trace["devices"][0]["ops"]
    assert devtrace.busy_ns(ops, lo, hi) == 286906
    assert devtrace.idle_gaps(ops, trace["host"], lo, hi) == [
        ["bench.serve", 0.008916263], ["bench.serve", 0.004190415],
        ["bench.serve", 0.004075767], ["bench.serve", 0.004048874],
        ["bench.serve", 0.003781145], ["bench.serve", 0.002593531],
        ["bench.serve", 0.002477727], ["bench.serve", 0.002384722],
        ["bench.serve", 0.002377661], ["bench.serve", 0.002297378]]


def test_recorded_dgn_flushes_break_down_by_stage():
    """Six DGN flushes recorded on the chip (the last three of one call,
    the first three of the next): nanosecond sums of each stage inside
    the window, read back exactly."""
    trace = _load("trace_dgn_screen_program.json")
    assert len(stages.flushes(trace)) == 6
    ms = stages.stage_ms(trace)
    sums = {"run": 103202703, "flush": 99387873, "eigvec": 31172991,
            "pack": 60198853, "layout": 43008512, "dispatch": 2010790,
            "compile": 0, "device_wait": 2818420, "d2h": 2719880, "unpack": 52670}
    for stage, ns in sums.items():
        assert ms[stage] == pytest.approx(ns / 6 / 1e6, rel=1e-12), stage
    assert ms["sched"] == pytest.approx((103202703 - 99387873) / 6 / 1e6, rel=1e-12)
    out = stages.reduce(trace, 6)
    assert out["stage_sum_ms_per_flush"] == pytest.approx(
        (103202703 - 99387873 + 31172991 + 60198853 + 2010790 + 2818420
         + 2719880 + 52670) / 6 / 1e6, rel=1e-12)
    # seven eager programs per flush, 31 of the 42 inside the layout plan
    assert out["eager_calls_per_flush"] == 7.0
    # 102,668,070 ns idle inside the serving calls, 98,431,421 of them
    # under a stage span
    assert out["unattributed_idle_share"] == pytest.approx(
        1 - 98431421 / 102668070, rel=1e-12)
    gaps = out["idle_gaps"]
    assert [g[0] for g in gaps] == ["repro.eigvec"] * 5 + ["repro.pack"] + \
        ["repro.layout"] * 4
    assert [g[1] for g in gaps[:2]] == [0.015031084, 0.011985978]
    assert gaps[-1][2] == 1.0
