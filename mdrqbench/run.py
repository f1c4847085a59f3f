"""Run one benchmark cell once; the last line of stdout is the result.

    python3 mdrqbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits nonzero, with no result line, when JAX finds no TPU, fewer chips than
the cell asks for, or kernels that would not run under Mosaic.
"""
import os
import sys
from pathlib import Path

# libtpu otherwise logs to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from mdrqbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
