"""Tier-1 twin of ``chip_bench/tests/test_negotiation_metrics.py`` (ISSUE 52):
the eight metric files that read ``negotiate_wait``, ``negotiate_recv``, the
rounds and the runtime threads' CPU are data, and their cases run on made-up
counters, so they cost tier-1 under a second.  Imported here so that tier-1
runs them; the instrument's own cases are in ``test_phase_spans.py``.
"""

import pytest

pytest.register_assert_rewrite("chip_bench.tests.test_negotiation_metrics")
from chip_bench.tests.test_negotiation_metrics import (  # noqa: E402,F401
    test_a_program_without_the_counters_reads_nothing_and_does_not_raise,
    test_a_tensors_way_adds_up_from_the_files,
    test_benchmark_holds_the_eight_entries_in_the_issues_order,
    test_entry_is_the_row_of_the_issues_table,
    test_entry_lists_only_eager_cells_and_each_cell_finds_it,
    test_every_counter_is_a_phase_of_the_program,
    test_file_is_one_delta_per_step_over_the_issues_counters,
    test_rank0_files_take_rank_0_and_max_files_the_largest,
    test_reads_the_worked_out_value_through_the_readers,
    test_the_pairs_differ_only_in_their_rule,
)
