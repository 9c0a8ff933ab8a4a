import sys
from pathlib import Path

# the benchmark's package lives at the root of the checkout
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
