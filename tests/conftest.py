import os
import sys

import pytest

# tests must see the real device count (1), NOT the dry-run's 512 — the
# dry-run sets its flag itself, in its own process.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Suite split (markers registered in pytest.ini): the data-plane modules
# need JAX; everything else is the stdlib-only control plane, which
# `pytest -m "not data_plane"` selects on runners without JAX.
DATA_PLANE_MODULES = {"test_kernels", "test_kernels_smoke", "test_arch_smoke",
                      "test_train_serve", "test_sharding_rules",
                      "test_tpu_compile"}


def pytest_collection_modifyitems(items):
    for item in items:
        module = item.module.__name__.rpartition(".")[2]
        if module in DATA_PLANE_MODULES:
            item.add_marker(pytest.mark.data_plane)
        else:
            item.add_marker(pytest.mark.control_plane)

# Toolchain-less runners (e.g. the GitHub control-plane job) have no JAX at
# all: skip collecting the data-plane modules entirely — marker deselection
# happens after import, which would already have crashed the run.
try:
    import jax  # noqa: E402

    jax.config.update("jax_platform_name", "cpu")
except ModuleNotFoundError:
    collect_ignore = [f"{m}.py" for m in DATA_PLANE_MODULES]
