import os
import sys

# Tests run on the default single CPU device (a caller's host-device-count
# flag must NOT leak here). Subprocess-based tests set their own XLA_FLAGS.
os.environ.pop("XLA_FLAGS", None)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
