# NOTE: deliberately NO XLA_FLAGS device-count override here — smoke tests
# and benches must see 1 device (the 512-device mesh exists only inside
# launch/dryrun.py and the subprocess-based elastic/sharding tests).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
