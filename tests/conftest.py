import sys
import os

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# NOTE: no XLA_FLAGS here on purpose — tests run on the single real CPU
# device; only launch/dryrun.py forces 512 host devices (per spec).
