"""tools/first_step_split.py's reduction of jax's monitoring events to the
parts of a first step, on a hand-made event list."""

import first_step_split
from first_step_split import CACHE_LOAD, COMPILE, LOWER, TRACE


def test_split_finds_the_step_program_among_the_others():
    events = [
        # (clock at the event's end, event, seconds, function)
        (10.0, TRACE, 0.5, "init"), (10.6, LOWER, 0.6, "jit(init)"),
        (11.0, COMPILE, 0.4, "jit(init)"),
        # the step: two kernels traced inside its trace
        (20.3, TRACE, 0.2, "gmm"), (20.9, TRACE, 0.3, "tgmm"),
        (22.0, TRACE, 2.0, "step"), (22.7, LOWER, 0.7, "jit(step)"),
        (23.0, CACHE_LOAD, 0.25, ""), (23.1, COMPILE, 0.4, "jit(step)"),
        # the reference's program afterwards lowers for longer
        (40.0, TRACE, 1.0, "body"), (43.0, LOWER, 3.0, "jit(body)"),
        (43.5, CACHE_LOAD, 0.45, ""), (43.6, COMPILE, 0.6, "jit(body)"),
    ]
    got = first_step_split.split(events)
    assert got["program"] == "jit(step)"
    assert (got["trace_s"], got["lower_s"]) == (2.0, 0.7)
    assert (got["compile_or_load_s"], got["cache_load_s"]) == (0.4, 0.25)
    assert got["nested_traces"] == 2
    assert got["nested_trace_s"] == {"tgmm": 0.3, "gmm": 0.2}


def test_split_without_a_step_program_is_empty():
    assert first_step_split.split([(1.0, LOWER, 0.1, "jit(other)")]) == {}
