"""The scanned-stack cache contract (DESIGN.md §3) under jax.checkpoint
remat of the layer-scan body, for each layer family."""
import pytest

from _model_helpers import assert_stacked_cache_bit_identity


# The slowest cases first: a test worker is handed its next file while it
# runs a file's last two tests.
@pytest.mark.parametrize("family,remat", [
    ("hybrid", "dots"), ("moe", "full"), ("attention", "dots"),
    ("attention", "full"), ("mla", "full"), ("ssm", "dots")])
def test_stacked_cache_bit_identity_remat(family, remat):
    """Same contract under jax.checkpoint remat of the scan body (the
    stacked cache entries are scan xs = saved inputs, never recomputed)."""
    assert_stacked_cache_bit_identity(family, "separable", remat=remat)
