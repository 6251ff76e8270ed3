"""Four kanana agents, one per (simulated) device, in pods of two: the
program's train step on a (pod, agent) mesh of 4 CPU devices follows
the plain group reference (``bench/group_ref.py``) through a local
step and a share window, and the reference with float8 projections in
the program's place reads not correct at the cell's limits."""
import pytest

from kanana_pods_small import correct, driver, limits, run_small, small


@pytest.mark.multi_device
def test_pods_step_matches_group_reference(multi_device):
    out = run_small(multi_device)
    assert out.attempted > 0 and out.failed == 0
    assert correct(out.compared), out.compared
    # float32 products at this size: far inside the cell's limits
    assert all(v < 1e-3 for v, _ in out.compared.values()), out.compared
    assert out.counters["expert_work"]["flops"] > 0


@pytest.mark.multi_device
def test_float8_control_is_not_correct(multi_device):
    cell, conf = small()
    fp8 = driver().control(cell, conf, 2**32 + 77,
                           multi_device[:4])["control_fp8"]
    lim = limits()
    assert any(fp8[k] > lim[k] for k in lim), (fp8, lim)
