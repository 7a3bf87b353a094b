"""CPU tests of the benchmark harness. The system under test lives in
`src/`, which the tests put on the path as the harness does."""
import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
