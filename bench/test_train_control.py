"""The training cell's control at a small size on the CPU: the plain
reference with float8 projections, put in the program's place, fails
at least one of the numbers the cell compares, at the cell's limits.
The same readings at the cell's own size on the chip come from
``bench/control.py``."""
import harness
from test_train_faults import CELL, small


def test_float8_control_is_not_correct():
    cell, conf = small()
    driver = harness.load_module(
        harness.os.path.join(harness.BENCH_DIR, "drivers", "train.py"),
        "bench_driver_train")
    readings = driver.control(cell, conf, seed=2**32 + 77)
    limits = harness.load_json(harness.BENCH_DIR, "workloads",
                               CELL + ".json")["check"]["limits"]
    fp8 = readings["control_fp8"]
    assert any(fp8[k] > limits[k] for k in limits), (fp8, limits)
