"""The command-line entry points as a caller in the same process uses
them (``chip_smoke.py`` does): train → checkpoint → group serving, the
depth cut, the mesh constructor and the compile-cache location."""
from __future__ import annotations

import math
import os

import jax
import pytest
from jax.sharding import AxisType

from repro.configs import get_arch_config

TRAIN = ["--arch", "mamba2-780m", "--agents", "2", "--batch", "1",
         "--seq", "32", "--threshold", "1", "--minibatch", "2"]


def test_train_then_serve_from_checkpoint(tmp_path):
    from repro.launch import serve, train
    ckpt = str(tmp_path / "group.npz")
    run = train.main(TRAIN + ["--steps", "3", "--ckpt", ckpt])
    assert [st["shared"] for st in run.steps] == [False, False, True]
    for st in run.steps:
        assert st["loss"].shape == (2,)
        assert all(math.isfinite(float(x)) for x in st["loss"])
        assert st["seconds"] > 0
    assert run.compile_s > 0 and run.params_per_agent > 0
    if jax.default_backend() == "cpu":   # kernels are TPU-only
        assert "tpu_custom_call" not in run.compiled.as_text()

    vocab = get_arch_config("mamba2-780m").reduced().vocab_size
    out = serve.main(["--arch", "mamba2-780m", "--requests", "3",
                      "--ckpt", ckpt, "--serve", "engine=group",
                      "--serve", "agents=2", "--serve", "slots=2",
                      "--serve", "max_new_tokens=3"])
    assert sorted(out) == [0, 1, 2]
    assert all(len(t) == 3 and all(0 <= x < vocab for x in t)
               for t in out.values())


@pytest.mark.parametrize("main", ["train", "serve"])
def test_layers_needs_full(main):
    from repro.launch import serve, train
    entry = {"train": train.main, "serve": serve.main}[main]
    with pytest.raises(SystemExit):
        entry(["--arch", "mamba2-780m", "--layers", "2"])


@pytest.mark.parametrize("arch", ["mamba2-780m", "yi-34b",
                                  "deepseek-v2-lite-16b"])
def test_with_layers_cuts_depth_only(arch):
    cfg = get_arch_config(arch)
    cut = cfg.with_layers(2)
    assert cut.n_layers == 2
    assert cut.first_k_dense <= 2
    assert cut.with_(n_layers=cfg.n_layers,
                     first_k_dense=cfg.first_k_dense) == cfg
    with pytest.raises(ValueError, match="layers"):
        cfg.with_layers(cfg.n_layers + 1)
    with pytest.raises(ValueError, match="layers"):
        cfg.with_layers(0)


def test_with_layers_rejects_hybrid():
    with pytest.raises(ValueError, match="super-blocks"):
        get_arch_config("zamba2-7b").with_layers(2)


def test_make_mesh_axes_are_auto():
    from repro.launch.mesh import make_mesh, make_pod_mesh
    for mesh in (make_mesh((1, 1), ("data", "model")), make_pod_mesh(1)):
        assert set(mesh.axis_types) == {AxisType.Auto}


def test_compile_cache_location(monkeypatch):
    from repro.common import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
