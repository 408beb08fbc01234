import os
import sys
from pathlib import Path

# Make `repro` importable regardless of how pytest is invoked.
SRC = Path(__file__).resolve().parent.parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

# Tests must see the device topology of the invoking environment (CI tier-1
# sets 4 fake CPU host devices); the 512-device fake platform is dryrun.py-
# only per the launch contract.  Keep matmul determinism on.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
