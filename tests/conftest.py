import os
import subprocess
import sys

import hypothesis
import jax
import pytest

# CPU tests run in fp32 (reduced configs set this too); keep x64 off.
jax.config.update("jax_enable_x64", False)

# ---------------------------------------------------------------------
# multi-device simulation rig: tests marked ``multi_device`` need >= 8
# devices, which on CPU only exist if XLA_FLAGS carried
# --xla_force_host_platform_device_count *before jax was imported*.
# When the current process is already multi-device (the CI
# multi-device lane, or a dev running with the flag set) the fixture
# is a no-op and the test runs inline. Otherwise the fixture re-execs
# just that test in a subprocess with the flag set — the only way to
# get the flag in front of the jax import — and reports the child's
# verdict.
# ---------------------------------------------------------------------
MULTI_DEVICE_COUNT = 8
_CHILD_ENV = "REPRO_MULTI_DEVICE_CHILD"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def multi_device(request):
    """Devices of the >= 8-device (simulated) platform; re-execs the
    test under XLA_FLAGS when the current process is single-device."""
    if jax.device_count() >= MULTI_DEVICE_COUNT:
        return jax.devices()
    if os.environ.get(_CHILD_ENV):
        pytest.fail(
            f"re-exec child still sees {jax.device_count()} device(s) "
            f"— XLA_FLAGS did not land before the jax import")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={MULTI_DEVICE_COUNT}"
    ).strip()
    env[_CHILD_ENV] = "1"
    # the parent may sit on a machine with an accelerator it already
    # holds; the simulated devices live on the host platform
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO_ROOT, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    res = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         "-p", "no:cacheprovider", request.node.nodeid],
        cwd=_REPO_ROOT, env=env, text=True, timeout=900,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    if res.returncode != 0:
        pytest.fail(
            f"multi-device re-exec failed "
            f"(XLA_FLAGS={env['XLA_FLAGS']!r}):\n{res.stdout}",
            pytrace=False)
    pytest.skip(f"passed under re-exec with {MULTI_DEVICE_COUNT} "
                f"simulated devices")


# Bounded CI profile: per-test @settings(max_examples=...) caps are
# tuned for thoroughness; the CI fast lane trades examples for wall
# time so the whole lane stays inside its ~5 min budget. deadline is
# off in both profiles — first-call jit compilation blows any
# per-example deadline.
hypothesis.settings.register_profile(
    "ci", max_examples=10, deadline=None, derandomize=True)
hypothesis.settings.register_profile(
    "dev", max_examples=40, deadline=None)
hypothesis.settings.load_profile("ci" if os.environ.get("CI") else "dev")
