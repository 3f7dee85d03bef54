import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src", ROOT / "chipbench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
