import os
import sys

# Tests must see the real single-device CPU backend (the 512-device override
# is reserved for the dry-run); make sure nothing leaks in.
os.environ.pop("XLA_FLAGS", None)

# Repo root on sys.path so `from tools.hydralint import locksan` resolves
# regardless of how pytest was launched (PYTHONPATH=src only adds src/).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def bf16_params(prog, seed: int = 0):
    from repro.launch.serve import make_params
    return make_params(prog.cfg, seed)
