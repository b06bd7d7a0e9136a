"""Test config: run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (SURVEY.md test strategy; the reference's
CPU-default + context-parametrized pattern, tests/python/gpu/test_operator_gpu.py)."""
import os

# Cross-context oracle mode (tools/cross_context_check.py): keep BOTH the
# accelerator and CPU platforms registered and run the op families under the
# TPU default context — the reference's test_operator_gpu.py trick of
# re-running the CPU suite under a second context (SURVEY §4).
_CROSS_CTX = os.environ.get("MXNET_TPU_CROSS_CTX") == "1"

if not _CROSS_CTX:
    # The tests run on a virtual 8-device CPU mesh, never on a chip: set
    # before jax is imported, so that it is what the first backend
    # initialization sees (XLA_FLAGS is read when the CPU client starts).
    _flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
              if not f.startswith("--xla_force_host_platform_device_count")]
    os.environ["XLA_FLAGS"] = " ".join(
        _flags + ["--xla_force_host_platform_device_count=8"])
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax

if not _CROSS_CTX and (len(jax.devices()) < 8 or
                       jax.devices()[0].platform != "cpu"):  # pragma: no cover
    raise RuntimeError("test process failed to get the 8-device CPU mesh: "
                       f"{jax.devices()}")

import warnings

warnings.filterwarnings("ignore", message=".*donated buffers.*")
warnings.filterwarnings("ignore", message=".*Some donated buffers were not usable.*")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); run "
        "explicitly with -m slow")


@pytest.fixture
def ctx():
    import mxnet_tpu as mx
    return mx.tpu(0) if _CROSS_CTX else mx.cpu()


if _CROSS_CTX:
    @pytest.fixture(autouse=True)
    def _tpu_default_context():
        """Every test runs with the accelerator as the default context, so all
        nd/np creations and eager ops exercise the TPU lowering while the
        numpy-side expected values stay host-computed — the CPU<->TPU oracle."""
        import mxnet_tpu as mx
        with mx.tpu(0):
            yield
