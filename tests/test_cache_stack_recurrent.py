"""The quantized-operand cache contract (DESIGN.md §3) for whole models:
SSM and hybrid stacks. With the weight cache threaded through the grouped
layer scans (stacked PreparedOperands as scan xs), loss AND grads must
equal the TFConfig.cache=False execution in all three modes.

The family x mode grid is split over test_cache_stack.py and this file,
so that no one file keeps a test worker busy for long. The slowest family
comes first: a test worker is handed its next file while it runs a file's
last two tests."""
import pytest

from _model_helpers import MODES, assert_stacked_cache_bit_identity


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("family", ["hybrid", "ssm"])
def test_stacked_cache_bit_identity(family, mode):
    """Loss AND grads with the stacked scan cache on == TFConfig.cache=False
    for every layer family, in every mode."""
    assert_stacked_cache_bit_identity(family, mode)
