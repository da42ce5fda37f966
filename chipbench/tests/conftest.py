"""The benchmark's own tests: ``python -m pytest chipbench/tests`` from the
repository root (the repository's ``pytest.ini`` collects ``tests/`` only,
so these run by hand).  They run on the CPU."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))
