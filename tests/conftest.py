"""Test env: 8 virtual CPU devices so mesh/sharding/collective behavior gets
real multi-device coverage without a TPU (SURVEY.md §4)."""

import os

# Must happen before the first backend initialization.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")  # silence AOT-cache noise

import jax  # noqa: E402
import pytest  # noqa: E402

# Runtime contract checkers (docs/analysis.md): compile-flat marker +
# compile_watch / device_gets fixtures for the whole suite.
pytest_plugins = ("tpuic.analysis.pytest_plugin",)

# Persistent XLA compilation cache: model-sized CPU compiles dominate suite
# time (minutes each); cache hits cut reruns to seconds. Keyed to the machine
# that wrote it — .gitignored, safe to delete any time.
from tpuic.compiled.cache import enable_compile_cache  # noqa: E402

enable_compile_cache()


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def imagefolder(tmp_path_factory):
    from tpuic.data.synthetic import make_synthetic_imagefolder
    root = tmp_path_factory.mktemp("data")
    return str(make_synthetic_imagefolder(str(root), classes=("a", "b", "c"),
                                          per_class=6, size=32))
