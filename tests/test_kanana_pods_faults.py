"""The kanana pods cell's planted faults on 4 (simulated) devices: a
share step that combines each agent's own window alone, a loss over
half of each agent's tokens, and a window sketch of half the weights
each read not correct against the plain group reference."""
import pytest

from kanana_pods_small import correct, run_small


@pytest.mark.multi_device
@pytest.mark.parametrize("fault", ["no_exchange", "half_batch",
                                   "sketch_half"])
def test_fault_is_not_correct(multi_device, fault):
    out = run_small(multi_device, fault=fault)
    assert not correct(out.compared), (fault, out.compared)
