"""The two-pass dataflow on 16-bit containers: a 16-bit row pass and a
32-bit column pass, taps baked into the kernel and taps traced into SMEM,
byte-equal to each other and to the plain int64 reference (the cases of
`test_container16.py`, in a file of their own so test workers share
them)."""
import pytest

from repro.filters import FILTER_BANK
from test_container16 import check_baked_and_smem

SEPARABLE = [name for name, spec in FILTER_BANK.items() if spec.separable]


@pytest.mark.parametrize("name", SEPARABLE)
def test_two_pass_baked_and_smem_taps_equal_the_plain_reference(name):
    check_baked_and_smem(name, "two_pass")
