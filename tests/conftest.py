import os
import sys

# Tests must see the default single CPU device (the dry-run sets its own
# virtual device count in a separate process). Keep threads tame on CI.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

# Property tests use hypothesis; fall back to the deterministic shim when the
# real library is not baked into the image (see tests/_hypothesis_shim.py).
try:
    import hypothesis  # noqa: F401
except ImportError:
    import _hypothesis_shim

    sys.modules["hypothesis"] = _hypothesis_shim
    sys.modules["hypothesis.strategies"] = _hypothesis_shim.strategies
