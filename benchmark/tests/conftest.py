"""The benchmark's own tests run on the CPU, by name (`python -m pytest
benchmark/tests -q`); they are not part of the repo's tier-1 suite."""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, for the cases that build a tensor-parallel mesh
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


@pytest.fixture
def presets():
    """The program's table, put back as it was after the case."""
    from llm_consensus_tpu.models.config import MODEL_PRESETS

    before = dict(MODEL_PRESETS)
    yield MODEL_PRESETS
    MODEL_PRESETS.clear()
    MODEL_PRESETS.update(before)
