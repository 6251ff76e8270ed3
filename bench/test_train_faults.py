"""The training driver at a small size on the CPU, with the chip check
skipped: a sound run comes out correct, and each fault planted under
the timed path comes out not correct against the cell's own limits."""
import copy
import json
import os
import sys
import time

import pytest

import harness

sys.path.insert(0, os.path.join(harness.ROOT, "src"))

CELL = "train.mamba2-780m.share2"


def small(name=CELL):
    cell = harness.load_json(harness.BENCH_DIR, "workloads", name + ".json")
    conf = harness.load_json(harness.BENCH_DIR, "configs",
                             cell["config"] + ".json")
    conf = copy.deepcopy(conf)
    conf.update(d_model=64, n_layer=2, vocab_size=250,
                compute_dtype="float32")
    conf["ssm_cfg"].update(d_state=16, headdim=16, chunk_size=32)
    cell = copy.deepcopy(cell)
    cell["traffic"].update(seq=64)
    if "relevance_sketch_dim" in cell["traffic"]["exchange"]:
        cell["traffic"]["exchange"]["relevance_sketch_dim"] = 128
    return cell, conf


def run_small(fault, name=CELL, seed=2**33 + 5):
    import jax
    cell, conf = small(name)
    bench = harness.Bench(name, seed, 0.5, False, time.time(),
                          benchmark={}, cell=cell, config=conf,
                          chips=jax.devices()[:cell["chips"]])
    driver = harness.load_module(
        os.path.join(harness.BENCH_DIR, "drivers", "train.py"),
        "bench_driver_train")
    out = driver.run(bench, fault=fault)
    print(json.dumps({"fault": fault, "compared": out.compared}))
    return out


def correct(compared):
    return all(v <= lim for v, lim in compared.values())


def test_sound_run_is_correct():
    out = run_small(None)
    assert out.attempted > 0 and out.failed == 0
    assert correct(out.compared), out.compared


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange",
                                   "sketch_half"])
def test_fault_is_not_correct(fault):
    assert not correct(run_small(fault).compared), fault
