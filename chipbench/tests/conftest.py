"""The benchmark's own tests, on the CPU at test size:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

(not collected by the repo's ``pytest.ini``, whose testpaths is tests/)."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
