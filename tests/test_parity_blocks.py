"""Tier-1 runs the cases of the benchmark's blocked logits comparison.

``parity.errors_blocked`` decides ``correct`` for every model compared past
1,024 positions, and a program PR can break what it walks (a seam between
blocks, ``row_end``, a traced start) without touching ``benchmark/``. The
cases stand in ``benchmark/tests/test_parity_blocks.py``, which the
benchmark's own run collects; here they are imported by name, with the two
fixtures they ask for, so that this suite collects them too.
"""

from benchmark.tests.conftest import presets  # noqa: F401
from benchmark.tests.test_parity_blocks import (  # noqa: F401
    short_blocks,
    test_a_long_sequence_needs_a_reference_that_computes_in_blocks,
    test_a_malformed_parity_object_stops_the_child_by_name,
    test_a_models_lengths_fall_back_to_the_files_then_to_the_defaults,
    test_block_path_equals_whole_path_in_float32,
    test_block_path_equals_whole_path_on_a_tensor_parallel_mesh,
    test_blocked_attention_equals_its_whole_form,
    test_forward_is_the_head_on_every_row_of_hidden,
    test_latent_attention_is_computed_in_blocks_of_queries_and_heads,
    test_the_check_never_holds_more_than_a_block_of_logits,
    test_the_length_alone_decides_whole_or_blocks,
    test_the_long_parity_rehearsal_compares_its_two_models_at_different_lengths,
    test_the_real_numbers_send_the_rehearsals_long_model_through_blocks,
)
