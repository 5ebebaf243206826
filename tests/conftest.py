# NOTE: no XLA_FLAGS here — smoke tests and benches must see the real
# single CPU device; tests that need a mesh force host devices in a
# subprocess.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
