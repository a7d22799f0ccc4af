"""The program under test is imported from ``src/`` beside the
benchmark, as ``chipbench.run`` imports it."""
import sys

from chipbench import spec

if str(spec.REPO / "src") not in sys.path:
    sys.path.insert(0, str(spec.REPO / "src"))
