import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
DATA = os.path.join(BENCH, "tests", "data")


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout root holding the tiny test cells (their compile cache
    then lands in the temporary directory)."""
    root = tmp_path / "root"
    shutil.copytree(DATA, root, ignore=shutil.ignore_patterns(
        "*.xplane.pb", ".jax_cache"))
    return str(root)
