import os
import sys

# Tests must see the normal 1-device CPU environment (the dry-run sets its
# own flags in a separate process). Keep threads tame on the 1-core box.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
