"""decode_greedy_tick_share on hand-made windows: the engine's counters as
the window opens and closes."""
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import cell  # noqa: E402
import window  # noqa: E402

READ = cell.metric_reader("decode_greedy_tick_share")


def ctx(stats_open, stats_close):
    return types.SimpleNamespace(window=window.Window(
        0.0, 1.0, [], 0, 1.0, stats_open, stats_close))


def test_an_all_greedy_window_reads_100():
    assert READ(ctx({"ticks": 40, "greedy_ticks": 40},
                    {"ticks": 140, "greedy_ticks": 140})) == 100.0


def test_a_window_with_sampled_ticks_reads_their_complement():
    assert READ(ctx({"ticks": 0, "greedy_ticks": 0},
                    {"ticks": 200, "greedy_ticks": 150})) == 75.0


def test_without_the_counter_or_a_tick_it_reads_nothing():
    assert READ(ctx({"ticks": 40}, {"ticks": 140})) is None
    assert READ(ctx({"ticks": 7, "greedy_ticks": 7},
                    {"ticks": 7, "greedy_ticks": 7})) is None
