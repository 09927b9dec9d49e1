"""The benchmark's trace reduction and per-layer readers, collected here
so that the repository's test run guards them: the tests of
benchmarks/lark_bench/tests/test_stages.py (stage and span reduction,
op names, the six stage and phase readers) and the trace and reader
tests of benchmarks/lark_bench/tests/test_lark_bench.py.  All run on the
CPU in seconds; the harness's end-to-end tests stay where they are.
"""
import importlib.util
import os

_TESTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "lark_bench", "tests")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "lark_bench_" + name, os.path.join(_TESTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_stages = _load("test_stages")
_bench = _load("test_lark_bench")

zoo = _stages.zoo
test_stage_of = _stages.test_stage_of
test_recorded_ops_fall_under_their_stage = \
    _stages.test_recorded_ops_fall_under_their_stage
test_stage_time_is_leaf_op_time = _stages.test_stage_time_is_leaf_op_time
test_recorded_call_phases = _stages.test_recorded_call_phases
test_phase_idle_by_brute_force = _stages.test_phase_idle_by_brute_force
test_recorded_phases_read_as_on_the_chip = \
    _stages.test_recorded_phases_read_as_on_the_chip
test_readers_on_recorded_trace = _stages.test_readers_on_recorded_trace
test_readers_silent_without_names = \
    _stages.test_readers_silent_without_names
test_absent_stage_and_extra_call_are_silent = \
    _stages.test_absent_stage_and_extra_call_are_silent
test_op_names_read_from_event_metadata = \
    _stages.test_op_names_read_from_event_metadata
test_trace_file_is_found_by_its_window = \
    _stages.test_trace_file_is_found_by_its_window

test_reducer_on_recorded_trace = _bench.test_reducer_on_recorded_trace
test_union_and_gaps = _bench.test_union_and_gaps
test_metric_readers = _bench.test_metric_readers
test_readers_find_nothing = _bench.test_readers_find_nothing
test_roofline_without_byte_count_is_silent = \
    _bench.test_roofline_without_byte_count_is_silent
test_kernel_byte_counts = _bench.test_kernel_byte_counts
test_kernel_events_are_classified = _bench.test_kernel_events_are_classified
