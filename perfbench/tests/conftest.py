"""The benchmark's own checks run on the CPU, with the benchmark's
directory and the program's ``src`` on the path."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parent.parent / "src"))
