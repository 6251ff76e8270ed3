#!/usr/bin/env python3
"""Bring-up smoke test: the DDAL main path on a TPU, through the
launchers a user calls.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # pod dispatch on a 2x2 host

One chip runs three phases in this one process:

1. train — ``repro.launch.train.main``: two mamba2-780m agents at the
   published widths, depth cut to ``LAYERS`` whole layers, sketched
   gradient-cosine relevance (the compiled ``grad_sketch`` kernel),
   enough steps for one eq. 4 share step, params saved to a
   checkpoint. Checks: finite losses, a share step, and a
   ``tpu_custom_call`` in the compiled step (a kernel ran compiled,
   not interpreted and not as its XLA twin).
2. serve — ``repro.launch.serve.main``: the multi-tenant group engine
   loads that checkpoint and answers ``REQUESTS`` requests. Checks:
   every request got its tokens, every id inside the vocabulary.
3. agreement — the first training step on the chip and on the host
   CPU, same seed, short sequence, ``default_matmul_precision
   ("highest")``. The per-agent losses must agree within
   ``LOSS_RTOL``.

``--chips 4`` runs only pod dispatch: four agents, one per chip, in
two hierarchical pods of two on a (2, 2) ("pod", "agent") mesh, and
the same steps without collectives (the no-mesh decomposition) on the
host CPU; per-agent losses and a parameter checksum must agree.

Times printed are from one unrepeated run, not metrics. The last line
of standard output is one JSON object naming the device; any failed
check exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "mamba2-780m"
LAYERS = 4          # 2 agents' state + step temporaries: 14.2 GiB of
                    # 15.75 in the compile for a described v5e
REQUESTS = 4
NEW_TOKENS = 16     # repro.launch.serve's default max_new_tokens
# bf16 keeps 8 significant bits: each rounding is off by up to 2^-9
# relative, and the chip and the CPU round different partial sums of
# each layer. The loss averages those errors over every token, so a
# few roundings' worth (1e-2 ≈ 5 · 2^-9) bounds it with room to spare.
LOSS_RTOL = 1e-2
# --chips 4 runs the reduced config in fp32 at "highest" precision;
# the two sides differ by transcendental ulps and summation order,
# which Adam's normalisation can lift to ~1e-5 relative per step.
POD_RTOL = 1e-3


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


MODEL = ["--arch", ARCH, "--full", "--layers", str(LAYERS)]


def train_argv(*extra: str) -> list:
    return [*MODEL, "--agents", "2",
            "--exchange", "estimator=grad_cos+sketch",
            "--exchange", "relevance_sketch_dim=256", *extra]


def phase_train(ckpt: str, seed: int):
    from repro.launch import train
    # threshold 1, minibatch 2: step 0 warms up, step 2 shares
    run = train.main(train_argv(
        "--steps", "4", "--batch", "2", "--seq", "512",
        "--threshold", "1", "--minibatch", "2", "--ckpt", ckpt,
        "--seed", str(seed)))
    losses = [float(x) for st in run.steps for x in st["loss"]]
    check(all(math.isfinite(x) for x in losses),
          f"train: non-finite loss in {losses}")
    check(any(st["shared"] for st in run.steps),
          "train: no <shared> step ran")
    check("tpu_custom_call" in run.compiled.as_text(),
          "train: the compiled step holds no tpu_custom_call")
    print(f"train: params/agent={run.params_per_agent:,} "
          f"compile={run.compile_s:.1f}s step times (one run, not a "
          f"metric) "
          + " ".join(f"{st['seconds'] * 1e3:.1f}ms" for st in run.steps))
    return run


def phase_serve(ckpt: str, seed: int, vocab: int) -> None:
    from repro.launch import serve
    t0 = time.perf_counter()
    out = serve.main([*MODEL, "--requests", str(REQUESTS), "--ckpt", ckpt,
                      "--serve", "engine=group", "--serve", "agents=2",
                      "--serve", "slots=2", "--seed", str(seed)])
    dt = time.perf_counter() - t0
    check(sorted(out) == list(range(REQUESTS)),
          f"serve: answered {sorted(out)}, wanted {REQUESTS} requests")
    for rid, toks in out.items():
        check(len(toks) == NEW_TOKENS,
              f"serve: request {rid} got {len(toks)} tokens")
        check(all(0 <= t < vocab for t in toks),
              f"serve: request {rid} has ids outside [0, {vocab})")
    print(f"serve: {REQUESTS} requests x {NEW_TOKENS} tokens in "
          f"{dt:.1f}s incl. compile (one run, not a metric)")


def phase_agreement(seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import train
    argv = train_argv("--steps", "1", "--batch", "1", "--seq", "256",
                      "--seed", str(seed))
    with jax.default_matmul_precision("highest"):
        chip_run = train.main(argv)
        with jax.default_device(jax.devices("cpu")[0]):
            host_run = train.main(argv)
    chip, host = chip_run.steps[0]["loss"], host_run.steps[0]["loss"]
    rel = np.abs(chip - host) / np.abs(host)
    print(f"agreement: compile chip {chip_run.compile_s:.1f}s cpu "
          f"{host_run.compile_s:.1f}s; first-step losses chip "
          f"{chip.tolist()} cpu {host.tolist()} max rel diff "
          f"{rel.max():.2e} (tolerance {LOSS_RTOL})")
    check(bool(np.all(rel <= LOSS_RTOL)),
          f"agreement: chip and CPU losses differ by {rel.max():.2e}")


def _checksums(path: str, n_agents: int):
    import numpy as np
    with np.load(path) as data:
        leaves = [data[k] for k in sorted(data.files)
                  if k != "__step__"]
    return np.array([sum(float(np.abs(x[a]).sum(dtype=np.float64))
                         for x in leaves) for a in range(n_agents)])


def phase_pods(out_dir: str, seed: int) -> None:
    import jax
    import numpy as np

    from repro.launch import train
    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, found {len(jax.devices())}")

    def argv(mesh: str, ckpt: str) -> list:
        return ["--arch", ARCH, "--agents", "4", "--mesh", mesh,
                "--exchange", "topology=hierarchical",
                "--exchange", "degree=2", "--exchange", "pods=2",
                "--steps", "4", "--batch", "2", "--seq", "64",
                "--threshold", "1", "--minibatch", "2",
                "--ckpt", ckpt, "--seed", str(seed)]

    mesh_ck = os.path.join(out_dir, "pods.npz")
    host_ck = os.path.join(out_dir, "host.npz")
    with jax.default_matmul_precision("highest"):
        mesh_run = train.main(argv("pods", mesh_ck))
        with jax.default_device(jax.devices("cpu")[0]):
            host_run = train.main(argv("single", host_ck))
    check(any(st["shared"] for st in mesh_run.steps),
          "pods: no <shared> step ran")
    hlo = mesh_run.compiled.as_text()
    check("all-gather" in hlo or "all-reduce" in hlo,
          "pods: the compiled step holds no collective")
    a = np.stack([st["loss"] for st in mesh_run.steps])
    b = np.stack([st["loss"] for st in host_run.steps])
    loss_rel = float((np.abs(a - b) / np.abs(b)).max())
    ca, cb = _checksums(mesh_ck, 4), _checksums(host_ck, 4)
    sum_rel = float((np.abs(ca - cb) / np.abs(cb)).max())
    print(f"pods: losses mesh {a.tolist()} cpu {b.tolist()}")
    print(f"pods: param checksums mesh {ca.tolist()} cpu {cb.tolist()}")
    print(f"pods: max rel diff losses {loss_rel:.2e} checksums "
          f"{sum_rel:.2e} (tolerance {POD_RTOL})")
    check(loss_rel <= POD_RTOL, "pods: losses disagree with the host")
    check(sum_rel <= POD_RTOL, "pods: params disagree with the host")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the pod-dispatch path and its host "
                        "reference")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    from repro.common.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from importlib.metadata import version
    print(f"jax {jax.__version__} jaxlib {version('jaxlib')} "
          f"libtpu {version('libtpu')} device {dev.device_kind} "
          f"x{len(jax.devices())} compile cache {cache}")

    from repro.configs import get_arch_config
    try:
        with tempfile.TemporaryDirectory() as out_dir:
            if args.chips == 4:
                phase_pods(out_dir, args.seed)
            else:
                ckpt = os.path.join(out_dir, "group.npz")
                phase_train(ckpt, args.seed)
                print(f"train: peak device memory "
                      f"{dev.memory_stats()['peak_bytes_in_use']:,} B")
                phase_serve(ckpt, args.seed,
                            get_arch_config(ARCH).vocab_size)
                phase_agreement(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED {e}", file=sys.stderr)
        return 1
    print(f"peak device memory {dev.memory_stats()['peak_bytes_in_use']:,}"
          f" B")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
