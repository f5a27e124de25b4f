"""The reduction from trace events to busy time, kernel time per program and
idle gaps, on hand-made events."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace_reduce as tr  # noqa: E402


def ev(name, start, end, **stats):
    return tr.Event(name, float(start), float(end), stats)


def test_busy_and_idle_add_up_to_the_window():
    ops = [ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40), ev("d", 90, 120)]
    lo, hi = 2, 100
    busy = tr.busy_ns(ops, lo, hi)
    idle = sum(e - s for s, e in tr.idle_gaps(ops, lo, hi))
    assert busy == 18 + 10 + 10
    assert busy + idle == hi - lo


def test_programs_from_the_replay_and_kernels_per_program():
    m0, m1, m2 = "jit_f(10)", "jit_f(11)", "jit_f(12)"
    mods = [ev(m0, 0, 10), ev(m1, 10, 20), ev(m0, 100, 110),
            ev(m1, 110, 120), ev(m2, 120, 125)]
    programs = tr.program_map(mods, (0, 25), ["decode", "prefill_chunk"],
                              "jit_f")
    assert programs == {m0: "decode", m1: "prefill_chunk"}
    pa = "%paged_attention.11 = f32[64,2,16,128] custom-call(s32[64,1] %a)"
    ops = [ev(pa, 101, 104), ev("%fusion.3 = f32[8] fusion(%b)", 104, 109),
           ev(pa, 111, 119), ev(pa, 121, 122)]
    dev = {"ops": ops, "modules": mods}
    assert tr.kernel_seconds(dev, "paged_attention", programs, "decode",
                             50, 200) == pytest.approx(3e-9)
    assert tr.kernel_seconds(dev, "paged_attention", programs,
                             "prefill_chunk", 50, 200) == pytest.approx(8e-9)


def test_eager_programs_between_the_replayed_steps_are_skipped():
    mods = [ev("jit__threefry_fold_in(9)", 0, 1), ev("jit_counted(1)", 1, 10),
            ev("jit_convert_element_type(7)", 10, 11),
            ev("jit_counted(2)", 11, 20), ev("jit_counted(3)", 20, 30)]
    programs = tr.program_map(mods, (0, 40),
                              ["decode", "decode", "prefill_chunk"],
                              "jit_counted")
    assert programs == {"jit_counted(1)": "decode",
                        "jit_counted(2)": "decode",
                        "jit_counted(3)": "prefill_chunk"}


def test_a_replay_that_cannot_tell_programs_apart_is_an_error():
    mods = [ev("jit_f(1)", 0, 10), ev("jit_f(1)", 10, 20)]
    with pytest.raises(ValueError):
        tr.program_map(mods, (0, 25), ["decode", "prefill_chunk"], "jit_f")


def test_gaps_are_named_by_the_innermost_host_span():
    ops = [ev("a", 0, 10), ev("b", 50, 60)]
    spans = [ev("bench.window", 0, 100), ev("bench.engine.step", 5, 55),
             ev("bench.engine.drain", 20, 45)]
    gaps = tr.top_gaps({"ops": ops, "modules": []}, spans, 0, 100)
    assert sorted(map(tuple, gaps)) == [
        ("bench.engine.drain", 40e-9),
        ("host: outside the engine's calls", 40e-9)]
