import os
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))


@pytest.fixture(autouse=True)
def _jax_env():
    """A rehearsal sets ``JAX_PLATFORMS`` and ``XLA_FLAGS`` for the JAX
    it would start (``run.rehearsal_env``); give this process's back
    after each test, so that no later child process inherits them."""
    saved = {name: os.environ.get(name) for name in ("JAX_PLATFORMS",
                                                     "XLA_FLAGS")}
    yield
    for name, value in saved.items():
        if value is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = value
