"""The kanana pods cell at a small size, for the CPU tests: a sound
run, a planted fault or the control of ``bench/drivers/train_pods.py``
on 4 of the (simulated) devices, against the cell's own limits."""
from __future__ import annotations

import copy
import os
import sys
import time

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)

import harness  # noqa: E402

CELL = "train.kanana2-30b-a3b.pods4"


def small():
    cell = copy.deepcopy(harness.load_json(BENCH, "workloads",
                                           CELL + ".json"))
    conf = copy.deepcopy(harness.load_json(BENCH, "configs",
                                           cell["config"] + ".json"))
    conf.update(hidden_size=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, kv_lora_rank=32, moe_intermediate_size=32,
                intermediate_size=96, router_outputs=16,
                n_routed_experts=4, first_held_expert=4, vocab_size=250,
                num_hidden_layers=3, compute_dtype="float32")
    cell["traffic"].update(seq=64)
    cell["traffic"]["exchange"]["relevance_sketch_dim"] = 128
    return cell, conf


def driver():
    return harness.load_module(
        os.path.join(BENCH, "drivers", "train_pods.py"),
        "bench_driver_train_pods")


def run_small(devices, fault=None, seed=2**33 + 5):
    cell, conf = small()
    bench = harness.Bench(CELL, seed, 0.5, False, time.time(),
                          benchmark={}, cell=cell, config=conf,
                          chips=list(devices[:cell["chips"]]))
    return driver().run(bench, fault=fault)


def limits():
    return harness.load_json(BENCH, "workloads",
                             CELL + ".json")["check"]["limits"]


def correct(compared):
    return all(v <= lim for v, lim in compared.values())
